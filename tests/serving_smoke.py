"""The engine the serving smokes share.

``test_gen_{load,tier,chaos,history}_stage_cpu_smoke`` each drive one
serving scenario end to end over the open-loop load generator
(``distllm_tpu.generate.loadgen``). They build their engine the way a
deployment does: weights made on the device and owned by the engine,
``attn_backend='auto'``, the pipelined loop, a warm-up, then one call.
The model is a toy in float32, so greedy tokens are bit-identical across
engines and runs and every check is one of identity or of counts: a CPU
says nothing about speed (PERF.md), and no smoke compares two clocks.
"""

from __future__ import annotations

import jax

from distllm_tpu.generate.engine import EngineConfig, LLMEngine, SamplingParams
from distllm_tpu.generate.loadgen import LoadgenConfig
from distllm_tpu.models import mistral

MODEL = mistral.MistralConfig(
    vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
    num_kv_heads=2, intermediate_size=128, dtype='float32',
)


class IdTokenizer:
    eos_id = None


def build_engine(warm: bool = True, **engine_kwargs) -> LLMEngine:
    """An engine over ``MODEL``, warmed unless the caller has a second or
    third engine that may as well compile what it dispatches (a warm-up is
    most of a smoke's seconds); ``engine_kwargs`` override the smokes'
    common ``EngineConfig``."""
    # Two rows and two prefill buckets: the warm-up grows with every shape.
    config = dict(
        block_size=8, num_blocks=64, max_num_seqs=2, max_model_len=64,
        prefill_min_bucket=32, decode_steps=4, pipeline_depth=2,
        enable_prefix_cache=True, attribution=True, attn_backend='auto',
    )
    config.update(engine_kwargs)
    engine = LLMEngine(
        MODEL,
        mistral.init_on_device(jax.random.PRNGKey(0), MODEL),
        IdTokenizer(),
        EngineConfig(**config),
        own_params=True,
    )
    try:
        if warm:
            engine.warmup()
        engine.generate_ids(
            [[1, 2, 3]], SamplingParams(temperature=0.0, max_tokens=2)
        )
    except Exception:
        engine.shutdown()
        raise
    return engine


def workload_config(**overrides) -> LoadgenConfig:
    """Warm sessions sharing a two-block prefix beside cold one-offs,
    greedy; the longest sequence (16 + 24 + 10) fits ``max_model_len``."""
    config = dict(
        seed=0, num_requests=24, rate_rps=50.0, num_sessions=3,
        warm_fraction=0.5, prefix_tokens=16, prompt_tokens=(4, 24),
        output_tokens=(4, 10), vocab_size=MODEL.vocab_size,
    )
    config.update(overrides)
    return LoadgenConfig(**config)
