"""``chip_smoke.py`` must not pass anywhere but on the chip.

The script's phases run on a TPU only (a tiny-size CPU rehearsal is for a
builder's hands, not for this tier); what tier-1 pins is the no-fallback
rule: with JAX held to the CPU the script exits non-zero, says "no TPU",
and prints no result line. The second test pins the defect the smoke's
long request found in the generator: a checkpoint directory as the script
writes it must serve prompts up to ``max_model_len``, not the first 512
tokens.
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS='cpu',
        # Nothing may land in the checkout from a test.
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'jax_cache'),
    )
    env.pop('XLA_FLAGS', None)
    proc = subprocess.run(
        [sys.executable, str(REPO / 'chip_smoke.py')],
        capture_output=True, text=True, timeout=300, cwd=tmp_path, env=env,
    )
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert 'no TPU' in proc.stdout + proc.stderr
    for line in proc.stdout.splitlines():
        if line.startswith('{'):
            assert 'ok' not in json.loads(line), line
    assert not (REPO / '.chip_smoke_work').exists()


def test_tpu_generator_admits_a_prompt_longer_than_512_tokens(tmp_path):
    import numpy as np

    from distllm_tpu.generate import get_generator

    spec = importlib.util.spec_from_file_location(
        'chip_smoke', REPO / 'chip_smoke.py'
    )
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    hf = dict(
        chip_smoke.MISTRAL_7B, vocab_size=1024, hidden_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        head_dim=16, intermediate_size=64,
    )
    # Like a published decoder, the tokenizer files carry HF's "unset"
    # sentinel for model_max_length; the context limit is the engine's.
    chip_smoke.write_mistral_checkpoint(tmp_path / 'model', hf, seed=0)
    generator = get_generator(
        {
            'name': 'tpu',
            'pretrained_model_name_or_path': str(tmp_path / 'model'),
            'max_model_len': 1024, 'num_blocks': 96, 'max_num_seqs': 2,
            'max_tokens': 2, 'temperature': 0.0,
            'enable_prefix_cache': True, 'prefill_chunk_tokens': 256,
        },
        register=False,
    )
    try:
        words = np.random.default_rng(0).integers(2, 1024, size=700)
        generator.generate(' '.join(f'w{i}' for i in words))
        engine = generator.engine
        assert engine.tokenizer.model_max_length == 1024
        assert engine.telemetry['prefix_lookup_tokens'] == 700
        assert engine.telemetry['prefill_chunks'] == 3
    finally:
        generator.shutdown()
