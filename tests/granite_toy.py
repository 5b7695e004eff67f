"""A toy ``granitemoehybrid`` (models/granite_hybrid.py) for the CPU tests:
the published config's keys at tiny widths, seeded weights, an engine over
it, and its row of the engine's contract (``test_engine_families.py``)."""

import jax
import numpy as np

from benchmarks import reference_granite as ref
from benchmarks.reference import token_gaps
from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
from distllm_tpu.models import granite_hybrid as gh

BLOCK = 4
LAYERS = ('mamba', 'mamba', 'attention', 'mamba')


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'granitemoehybrid', 'vocab_size': 64, 'hidden_size': 32,
        'layer_types': list(LAYERS), 'num_hidden_layers': len(LAYERS),
        'num_attention_heads': 4, 'num_key_value_heads': 2,
        'mamba_n_heads': 8, 'mamba_d_head': 8, 'mamba_d_state': 16,
        'mamba_d_conv': 4, 'mamba_chunk_size': 8, 'mamba_expand': 2,
        'mamba_n_groups': 1, 'intermediate_size': 16,
        'shared_intermediate_size': 24, 'num_local_experts': 8,
        'num_experts_per_tok': 3, 'embedding_multiplier': 12.0,
        'attention_multiplier': 0.25, 'residual_multiplier': 0.22,
        'logits_scaling': 4.0, 'rms_norm_eps': 1e-5,
        'position_embedding_type': 'nope', 'tie_word_embeddings': True,
    }
    hf.update(over)
    return hf


def tiny(seed=0, **over):
    hf = tiny_hf(**over)
    cfg = gh.GraniteHybridConfig.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = gh.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits.
    params = jax.tree.map(
        lambda a: a * 4.0 if a.ndim >= 3 and a.shape[-1] > 1 else a, params
    )
    return hf, cfg, params


def spread(a, b):
    """Largest difference as a share of the reference's spread."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / b.std())


class NoTokenizer:
    eos_id = None


def make_engine(seed=0, hf_over=None, **over):
    hf, cfg, params = tiny(seed, **(hf_over or {}))
    settings = dict(
        block_size=BLOCK, num_blocks=64, max_num_seqs=4, max_model_len=96,
        prefill_chunk_tokens=8, decode_steps=4, attn_backend='xla',
        enable_prefix_cache=False,
    )
    settings.update(over)
    engine = LLMEngine(cfg, params, NoTokenizer(), EngineConfig(**settings))
    return hf, params, engine


def prompt(rng, n):
    return [int(t) for t in rng.integers(0, 64, n)]


# ------------------------------------------ the row of the engine's contract
def token_gap(params, hf, ids, at, out):
    return max(token_gaps(ref.granite_logits(params, hf, ids), [at[0, 0] + 1], [out]))


def _after_greedy(engine, params, records, lengths, backend):
    assert engine.telemetry['state_pool_slots'] == 4
    # 3 Mamba layers x (8 x 8 x 16 float32 + 3 x 96 float32) a slot.
    assert engine.telemetry['state_pool_bytes'] == 4 * 3 * (1024 + 288) * 4


def left_errors(engine, hf, params, fed, record):
    """The SSM state in the slot a finished request held against the
    reference's after everything but the request's last token: the error a
    Mamba layer, and the first layer's over its slow heads (1 of 8)."""
    _, want = ref.granite_forward(params, hf, np.asarray(fed)[None], [len(fed)])
    slot = np.asarray([record['state_slot']])
    got = [np.asarray(leaf[slot]) for leaf in engine.state_pool.state['ssm']]
    rate = params['mamba']['dt_bias'][0], params['mamba']['A_log'][0]
    assert len(got) == 3 and len(ref.slow_heads(*rate)) == 1
    return ref.state_errors(got, want), ref.slow_head_state_error(got[0], want[0], *rate)


def _check_left(engine, hf, params, fed, record):
    errors, slow = left_errors(engine, hf, params, fed, record)
    assert max(errors) < 1e-5 and slow < 1e-5, (errors, slow)


def _check_sampled(engine, records):
    windows = [r for r in records if r['kind'] == 'decode']
    assert windows and all(0 < r['moe_pairs_held'] < r['moe_pairs'] for r in windows)
    # 4 layers x 3 picks a token: every decode token routes 12 pairs.
    assert sum(r['moe_pairs'] for r in windows) == 12 * sum(r['tokens'] for r in windows)


ENGINE_CASES = dict(
    refusal='cannot serve a hybrid',
    refused=('enable_prefix_cache', 'host_kv_tier_bytes', 'enable_mixed_batching',
             'draft_k', 'kv_cache_dtype=int8', 'quantization'),
    # shorter than, equal to and 2.5 times the prefill chunk (8); one length
    # through the Pallas interpreter is enough
    greedy=[(n, (n,), 'xla') for n in (5, 8, 20)] + [(20, (20,), 'interpret')],
    after_greedy=_after_greedy,
    left=dict(seed=3, lengths=(6, 19, 11), max_tokens=13, check=_check_left),
    windows=(1, ((6, 3), (19, 11))),
    reuse=(4, 13),  # one span, and chunks
    preempt=dict(seed=3, n=12, num_blocks=11),
    sampled=dict(
        hf_over=dict(num_local_experts=4, num_routed_experts=8), seed=4,
        lengths=(9, 30, 3), sampling=dict(temperature=0.7, top_p=0.9, max_tokens=9),
        check=_check_sampled,
    ),
    warm_prompt=10,
)
