"""Distributed embedding driver: file-sharded map over a compute fabric.

Reference parity: ``distllm/distributed_embedding.py`` — YAML config, glob
input files, ship a pure worker function to the pool, each worker:
registry-warmstarted encoder → dataset read → embed → write to a per-file
UUID output shard. Timer lines tag every stage exactly like the reference
(``distributed_embedding.py:45-80``) so existing log tooling keeps working.

Run: ``python -m distllm_tpu.distributed_embedding --config embed.yaml``
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


def embedding_worker(
    file: str,
    output_dir: str,
    dataset_kwargs: dict[str, Any],
    encoder_kwargs: dict[str, Any],
    pooler_kwargs: dict[str, Any],
    embedder_kwargs: dict[str, Any],
    writer_kwargs: dict[str, Any],
) -> str:
    """Embed one input file into a fresh UUID output shard; returns the shard."""
    from distllm_tpu.embed import (
        get_dataset,
        get_embedder,
        get_encoder,
        get_pooler,
        get_writer,
    )

    file_tag = Path(file).name
    with Timer('loaded-encoder', file_tag):
        encoder = get_encoder(encoder_kwargs, register=True)
    dataset = get_dataset(dataset_kwargs)
    pooler = get_pooler(pooler_kwargs)
    embedder = get_embedder(embedder_kwargs)
    writer = get_writer(writer_kwargs)

    with Timer('loaded-dataset', file_tag):
        corpus = dataset.read(file)
    with Timer('computed-embeddings', file_tag):
        result = embedder.embed(
            corpus, encoder, pooler, batch_size=dataset.config.batch_size
        )
    shard_dir = Path(output_dir) / uuid.uuid4().hex
    with Timer('wrote-embeddings', file_tag):
        writer.write(shard_dir, result)
    return str(shard_dir)


class Config(BaseConfig):
    """Driver configuration (reference: ``distributed_embedding.py:83-109``)."""

    input_dir: Path
    output_dir: Path
    glob_patterns: list[str] = ['*']
    dataset_config: dict[str, Any]
    encoder_config: dict[str, Any]
    pooler_config: dict[str, Any]
    embedder_config: dict[str, Any]
    writer_config: dict[str, Any]
    compute_config: ComputeConfigs = LocalConfig()


def run_embedding(config: Config) -> int:
    """Execute the driver for a parsed config (shared by module CLI + typer-
    style ``embed`` subcommand)."""
    embedding_dir = config.output_dir / 'embeddings'
    embedding_dir.mkdir(parents=True, exist_ok=True)
    # Audit copy for experiment tracking (reference :133).
    config.write_yaml(config.output_dir / 'config.yaml')

    files: list[str] = []
    for pattern in config.glob_patterns:
        files.extend(str(p) for p in sorted(config.input_dir.glob(pattern)))
    if not files:
        log_event(
            f'No input files matched {config.glob_patterns} in '
            f'{config.input_dir}',
            component='embed',
        )
        return 1
    log_event(f'Embedding {len(files)} files -> {embedding_dir}', component='embed')

    worker_fn = functools.partial(
        # Run as `python -m`, this module is __main__; rebind the
        # worker fn to its importable path so fabric workers can
        # unpickle it (Parsl has the same module-level-fn rule).
        canonical_function(embedding_worker, 'distllm_tpu.distributed_embedding'),
        output_dir=str(embedding_dir),
        dataset_kwargs=config.dataset_config,
        encoder_kwargs=config.encoder_config,
        pooler_kwargs=config.pooler_config,
        embedder_kwargs=config.embedder_config,
        writer_kwargs=config.writer_config,
    )
    executor = config.compute_config.get_executor(config.output_dir / 'run')
    shards = map_with_teardown(executor, worker_fn, files)
    log_event(f'Finished: {len(shards)} shards written', component='embed')
    return 0


def main(argv: list[str] | None = None) -> int:
    from distllm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', required=True, type=Path)
    args = parser.parse_args(argv)
    return run_embedding(Config.from_yaml(args.config))


if __name__ == '__main__':
    raise SystemExit(main())
