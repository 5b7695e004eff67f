"""Engine tensor parallelism on the virtual CPU mesh.

The reference delegates TP to vLLM (``tensor_parallel_size`` passthrough,
``distllm/generate/generators/vllm_backend.py:66-67``); here TP is a mesh
axis and the whole serving path — prefill, paged KV scatter, decode gather,
sampling — must produce the SAME tokens under GSPMD propagation as on one
device. Greedy decoding makes equality exact.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.models import mistral
from distllm_tpu.parallel.mesh import MeshSpec, make_mesh
from distllm_tpu.parallel.sharding import shard_pytree


class _Tok:
    eos_id = None


@pytest.fixture(scope='module')
def model():
    cfg = mistral.MistralConfig(
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=128,
        dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _generate(cfg, params, mesh, prompts, max_tokens=12):
    engine_cfg = EngineConfig(
        block_size=4,
        num_blocks=64,
        max_num_seqs=4,
        max_model_len=128,
        prefill_min_bucket=8,
    )
    if mesh is not None:
        params = shard_pytree(params, mistral.param_specs(cfg, params), mesh)
    engine = LLMEngine(cfg, params, _Tok(), engine_cfg, mesh=mesh)
    outs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=max_tokens)
    )
    engine.shutdown()
    return outs


def test_tp2_matches_single_device(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(1, cfg.vocab_size, size=n)) for n in (5, 17, 9, 26)
    ]

    single = _generate(cfg, params, None, prompts)
    mesh = make_mesh(MeshSpec(data=1, model=2), devices=jax.devices()[:2])
    tp = _generate(cfg, params, mesh, prompts)

    assert all(len(o) == 12 for o in single)
    assert single == tp


def test_tp4_matches_single_device(model):
    # num_kv_heads=2 < tp=4 must be rejected, not silently wrong.
    cfg, params = model
    mesh = make_mesh(MeshSpec(data=1, model=4), devices=jax.devices()[:4])
    with pytest.raises(ValueError, match='num_kv_heads'):
        _generate(cfg, params, mesh, [[1, 2, 3]])


def test_tp2_qwen2_biases_match_single_device():
    """Q/K/V biases (Qwen2 family) shard with their column-parallel
    kernels — the bias specs must keep TP token-exact, not just run."""
    cfg = mistral.MistralConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=128, attention_bias=True,
        dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(3), cfg)
    assert 'bias' in params['layers']['q']
    rng = np.random.default_rng(2)
    prompts = [
        list(rng.integers(1, cfg.vocab_size, size=n)) for n in (5, 18, 9)
    ]
    single = _generate(cfg, params, None, prompts)
    mesh = make_mesh(MeshSpec(data=1, model=2), devices=jax.devices()[:2])
    tp = _generate(cfg, params, mesh, prompts)
    assert single == tp


def test_tp2_with_continuous_batching_churn(model):
    """Requests joining/leaving the batch (staggered finishes) under TP."""
    cfg, params = model
    rng = np.random.default_rng(1)
    prompts = [
        list(rng.integers(1, cfg.vocab_size, size=n))
        for n in (3, 30, 7, 21, 12, 5)
    ]

    single = _generate(cfg, params, None, prompts, max_tokens=8)
    mesh = make_mesh(MeshSpec(data=1, model=2), devices=jax.devices()[:2])
    tp = _generate(cfg, params, mesh, prompts, max_tokens=8)

    assert single == tp


def test_tp2_gemma2_matches_single_device():
    """Gemma-2's extras (sandwich norms, softcaps, scaled embeddings,
    alternating windows) must stay token-exact under TP — the post norms
    are replicated and softcapping is elementwise on already-combined
    scores, so TP=2 greedy output must equal single-device."""
    from distllm_tpu.models import gemma

    cfg = gemma.GemmaConfig(
        name='gemma2', vocab_size=256, hidden_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
        max_position_embeddings=128, dtype='float32',
        activation='gelu_new', embedding_multiplier=64 ** 0.5,
        norm_plus_one=True, post_norms=True, query_scale=16 ** -0.5,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        sliding_window=6, sliding_window_pattern='alternating',
        tie_word_embeddings=True, rms_norm_eps=1e-6,
    )
    params = gemma.init(jax.random.PRNGKey(5), cfg)
    assert 'post_attn_ln' in params['layers']
    rng = np.random.default_rng(4)
    prompts = [
        list(rng.integers(1, cfg.vocab_size, size=n)) for n in (5, 18, 9)
    ]
    single = _generate(cfg, params, None, prompts)
    mesh = make_mesh(MeshSpec(data=1, model=2), devices=jax.devices()[:2])
    tp = _generate(cfg, params, mesh, prompts)
    assert single == tp


def test_tp2_pool_shards_hold_whole_heads(model):
    """The pool's dim 3 is a token's ``N_kv * Hd`` row, whole heads in
    contiguous runs: ``P(None, None, None, 'model')`` gives each of two
    shards one of the two KV heads, the split the attention heads have.
    Read after a served prompt: shard ``i`` is head ``i`` of every row."""
    cfg, params = model
    mesh = make_mesh(MeshSpec(data=1, model=2), devices=jax.devices()[:2])
    sharded = shard_pytree(params, mistral.param_specs(cfg, params), mesh)
    engine = LLMEngine(
        cfg, sharded, _Tok(),
        EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2,
                     max_model_len=32, prefill_min_bucket=8),
        mesh=mesh,
    )
    prompt = list(range(1, 9))
    engine.generate_ids([prompt], SamplingParams(temperature=0.0, max_tokens=2))
    head_dim = cfg.head_size
    assert engine.kv.k_pool.shape == (2, 16, 4, 2 * head_dim)
    shards = sorted(
        engine.kv.k_pool.addressable_shards, key=lambda s: s.index[3].start
    )
    assert [s.data.shape for s in shards] == [(2, 16, 4, head_dim)] * 2
    ids = np.asarray([prompt], np.int32)
    _, k_all, _ = mistral.prefill(params, cfg, ids, np.ones_like(ids))
    want = np.asarray(k_all)[:, 0]  # [L, 8, N_kv, Hd]
    written = np.asarray(engine.kv.k[0][np.arange(16)]).reshape(64, 2, head_dim)
    # the prompt's two blocks, wherever the allocator put them
    start = next(
        b * 4 for b in range(1, 16)
        if np.allclose(written[b * 4], want[0, 0], atol=1e-5)
    )
    np.testing.assert_allclose(written[start:start + 4], want[0, :4], atol=1e-5)
    for head, shard in enumerate(shards):
        np.testing.assert_allclose(
            np.asarray(shard.data)[0].reshape(64, head_dim)[start:start + 4],
            want[0, :4, head], atol=1e-5,
        )
    engine.shutdown()
