"""Distributed tokenization driver.

Reference parity: ``distllm/distributed_tokenization.py`` — tokenize jsonl
text files with an HF tokenizer into ``input_ids``/``attention_mask``
(+``labels`` when requested) and save per-file HF datasets. HF hub login via
dotenv is replaced by requiring local tokenizer files (zero-egress).

Run: ``python -m distllm_tpu.distributed_tokenization --config tok.yaml``
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import uuid
from pathlib import Path
from typing import Any

from distllm_tpu.observability.instruments import log_event
from distllm_tpu.parallel.fabric import map_with_teardown
from distllm_tpu.parallel.launcher import ComputeConfigs, LocalConfig
from distllm_tpu.timer import Timer
from distllm_tpu.utils import BaseConfig, canonical_function


class TokenizerConfig(BaseConfig):
    """Parity with ``distributed_tokenization.py:18-42``."""

    tokenizer_name_or_path: str
    text_field: str = 'text'
    max_length: int = 2048
    truncation: bool = True
    padding: bool | str = False
    return_labels: bool = False
    trust_remote_code: bool = False


def tokenizer_worker(
    file: str,
    output_dir: str,
    tokenizer_kwargs: dict[str, Any],
) -> str:
    """Tokenize one jsonl file into an HF dataset shard."""
    os.environ.setdefault('TOKENIZERS_PARALLELISM', '0')  # reference :96
    from datasets import Dataset
    from transformers import AutoTokenizer

    config = TokenizerConfig(**tokenizer_kwargs)
    file_tag = Path(file).name
    with Timer('loaded-tokenizer', file_tag):
        tokenizer = AutoTokenizer.from_pretrained(
            config.tokenizer_name_or_path,
            trust_remote_code=config.trust_remote_code,
        )

    with Timer('read-input', file_tag):
        texts = []
        with open(file) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    texts.append(json.loads(line)[config.text_field])

    with Timer('tokenized', file_tag):
        encoded = tokenizer(
            texts,
            truncation=config.truncation,
            max_length=config.max_length,
            padding=config.padding,
        )
        columns: dict[str, Any] = {
            'input_ids': encoded['input_ids'],
            'attention_mask': encoded['attention_mask'],
        }
        if config.return_labels:
            columns['labels'] = [list(row) for row in encoded['input_ids']]

    shard_dir = Path(output_dir) / uuid.uuid4().hex
    with Timer('wrote-dataset', file_tag):
        Dataset.from_dict(columns).save_to_disk(str(shard_dir))
    return str(shard_dir)


class Config(BaseConfig):
    input_dir: Path
    output_dir: Path
    glob_patterns: list[str] = ['*.jsonl']
    tokenizer_config: dict[str, Any]
    compute_config: ComputeConfigs = LocalConfig()


def run_tokenization(config: Config) -> int:
    dataset_dir = config.output_dir / 'tokenized'
    dataset_dir.mkdir(parents=True, exist_ok=True)
    config.write_yaml(config.output_dir / 'config.yaml')

    files: list[str] = []
    for pattern in config.glob_patterns:
        files.extend(str(p) for p in sorted(config.input_dir.glob(pattern)))
    if not files:
        log_event(
            f'No input files matched {config.glob_patterns} in '
            f'{config.input_dir}',
            component='tokenize',
        )
        return 1
    log_event(f'Tokenizing {len(files)} files -> {dataset_dir}', component='tokenize')

    worker_fn = functools.partial(
        # Run as `python -m`, this module is __main__; rebind the
        # worker fn to its importable path so fabric workers can
        # unpickle it (Parsl has the same module-level-fn rule).
        canonical_function(tokenizer_worker, 'distllm_tpu.distributed_tokenization'),
        output_dir=str(dataset_dir),
        tokenizer_kwargs=config.tokenizer_config,
    )
    executor = config.compute_config.get_executor(config.output_dir / 'run')
    shards = map_with_teardown(executor, worker_fn, files)
    log_event(f'Finished: {len(shards)} shards written', component='tokenize')
    return 0


def main(argv: list[str] | None = None) -> int:
    from distllm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--config', required=True, type=Path)
    args = parser.parse_args(argv)
    return run_tokenization(Config.from_yaml(args.config))


if __name__ == '__main__':
    raise SystemExit(main())
