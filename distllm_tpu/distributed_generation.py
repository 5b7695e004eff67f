"""Distributed generation driver: file-sharded map over a compute fabric.

Reference parity: ``distllm/distributed_generation.py`` — YAML config, glob
inputs, warmstarted generator per worker, responses postprocessed and
empty-response items dropped (``:69-75``), per-file UUID output shards, and
the guard that the output directory must NOT pre-exist (``:115-121``) so a
finished run is never clobbered.

Run: ``python -m distllm_tpu.distributed_generation --config generate.yaml``
"""

from __future__ import annotations

import argparse
import functools
import uuid
from pathlib import Path
from typing import Any

from distllm_tpu.observability.instruments import log_event
from distllm_tpu.parallel.fabric import map_with_teardown
from distllm_tpu.parallel.launcher import ComputeConfigs, LocalConfig
from distllm_tpu.timer import Timer
from distllm_tpu.utils import BaseConfig, canonical_function


def generate_worker(
    file: str,
    output_dir: str,
    reader_kwargs: dict[str, Any],
    prompt_kwargs: dict[str, Any],
    generator_kwargs: dict[str, Any],
    writer_kwargs: dict[str, Any],
) -> str:
    """Generate responses for one input file into a UUID output shard."""
    from distllm_tpu.generate import (
        get_generator,
        get_prompt_template,
        get_reader,
        get_writer,
    )

    file_tag = Path(file).name
    with Timer('loaded-generator', file_tag):
        generator = get_generator(generator_kwargs, register=True)
    reader = get_reader(reader_kwargs)
    prompt = get_prompt_template(prompt_kwargs)
    writer = get_writer(writer_kwargs)

    with Timer('read-input', file_tag):
        texts, paths = reader.read(file)
    with Timer('generated-responses', file_tag):
        prompts = prompt.preprocess(texts)
        raw = generator.generate(prompts)
        responses = prompt.postprocess(raw)
    # Drop items whose postprocessed response is empty (reference :69-75).
    kept = [
        (p, t, r) for p, t, r in zip(paths, texts, responses) if r
    ]
    paths, texts, responses = (
        [k[0] for k in kept],
        [k[1] for k in kept],
        [k[2] for k in kept],
    )
    shard_dir = Path(output_dir) / uuid.uuid4().hex
    with Timer('wrote-responses', file_tag):
        writer.write(shard_dir, paths, texts, responses)
    return str(shard_dir)


class Config(BaseConfig):
    """Driver configuration (reference: ``distributed_generation.py:89-121``)."""

    input_dir: Path
    output_dir: Path
    glob_patterns: list[str] = ['*']
    reader_config: dict[str, Any]
    prompt_config: dict[str, Any]
    generator_config: dict[str, Any]
    writer_config: dict[str, Any]
    compute_config: ComputeConfigs = LocalConfig()


def run_generation(config: Config) -> int:
    if config.output_dir.exists():
        # Clobber guard (reference :115-121).
        log_event(
            f'Output directory {config.output_dir} already exists; refusing '
            'to overwrite a finished run.',
            component='generate',
        )
        return 1
    generation_dir = config.output_dir / 'generations'
    generation_dir.mkdir(parents=True)
    config.write_yaml(config.output_dir / 'config.yaml')

    files: list[str] = []
    for pattern in config.glob_patterns:
        files.extend(str(p) for p in sorted(config.input_dir.glob(pattern)))
    if not files:
        log_event(
            f'No input files matched {config.glob_patterns} in '
            f'{config.input_dir}',
            component='generate',
        )
        return 1
    log_event(
        f'Generating over {len(files)} files -> {generation_dir}',
        component='generate',
    )

    worker_fn = functools.partial(
        # Run as `python -m`, this module is __main__; rebind the
        # worker fn to its importable path so fabric workers can
        # unpickle it (Parsl has the same module-level-fn rule).
        canonical_function(generate_worker, 'distllm_tpu.distributed_generation'),
        output_dir=str(generation_dir),
        reader_kwargs=config.reader_config,
        prompt_kwargs=config.prompt_config,
        generator_kwargs=config.generator_config,
        writer_kwargs=config.writer_config,
    )
    executor = config.compute_config.get_executor(config.output_dir / 'run')
    shards = map_with_teardown(executor, worker_fn, files)
    log_event(f'Finished: {len(shards)} shards written', component='generate')
    return 0


def main(argv: list[str] | None = None) -> int:
    from distllm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', required=True, type=Path)
    args = parser.parse_args(argv)
    return run_generation(Config.from_yaml(args.config))


if __name__ == '__main__':
    raise SystemExit(main())
