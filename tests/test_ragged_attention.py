"""Parity matrix for the fused ragged Pallas paged-attention kernel.

``ragged_paged_attention_pallas`` (interpret mode — the same kernel code
path Mosaic compiles on TPU, executed on CPU) is pinned against
``ragged_paged_attention_xla``, the always-available bit-exactness
baseline, across the full serving feature surface: decode rows × chunk
rows × GQA grouping × static/traced sliding windows × logit softcap ×
custom scale × ``q_lens`` padding × query tiling. The engine-level
greedy fp32 token-identity test at the bottom flips the backend under a
real serving loop (prefix cache + chunked prefill, so ragged spans and
decode spans both dispatch through the kernel).

Boundary being tested: VALID rows/queries must match the XLA path to
fp32 tolerance; PAD queries are exact zeros from the kernel (the XLA
twin emits finite key-0 garbage there) — both finite, both discarded by
every caller (docs/serving.md "Attention kernel backends").
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distllm_tpu.ops.paged_attention import (
    ragged_paged_attention_pallas,
    ragged_paged_attention_xla,
)


def _setup(rng, *, num_blocks=12, block_size=4, nkv=2, nh=4, hd=8, b=3,
           s=5):
    # Head-folded, as the pool stores a layer: [blocks, block_size, nkv*hd].
    k = jnp.asarray(
        rng.normal(size=(num_blocks, block_size, nkv * hd)).astype(np.float32)
    )
    v = jnp.asarray(
        rng.normal(size=(num_blocks, block_size, nkv * hd)).astype(np.float32)
    )
    max_blocks = 8
    # Block 0 is the trash block by engine convention; tables point at
    # arbitrary scattered real blocks like the paged allocator produces.
    bt = jnp.asarray(
        rng.integers(1, num_blocks, size=(b, max_blocks)), jnp.int32
    )
    # Row 0: mid-stream chunk; row 1: span == context (fresh prefill);
    # row 2: decode-like single live query (rest is q_lens padding).
    ctx = jnp.asarray([17, s, 9][:b], jnp.int32)
    q_lens = jnp.asarray([s, s, 1][:b], jnp.int32)
    q0 = ctx - q_lens
    pos = q0[:, None] + jnp.arange(s)[None, :]
    q = jnp.asarray(rng.normal(size=(b, s, nh, hd)).astype(np.float32))
    return q, k, v, bt, ctx, pos, q_lens


def _assert_parity(out, ref, q_lens, s):
    out, ref = np.asarray(out), np.asarray(ref)
    assert np.isfinite(out).all(), 'pallas emitted non-finite values'
    valid = np.arange(s)[None, :] < np.asarray(q_lens)[:, None]
    np.testing.assert_allclose(
        out[valid], ref[valid], atol=1e-5, rtol=1e-4
    )


@pytest.mark.parametrize('softcap', [None, 30.0], ids=['nocap', 'cap30'])
@pytest.mark.parametrize('scale', [None, 0.25], ids=['defscale', 'scale'])
def test_ragged_parity_softcap_and_scale(rng, softcap, scale):
    """gemma2 knobs: tanh logit softcap and query_pre_attn_scalar scale,
    with a sliding window riding along."""
    q, k, v, bt, ctx, pos, q_lens = _setup(rng)
    ref = ragged_paged_attention_xla(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=5,
        scale=scale, logit_softcap=softcap,
    )
    out = ragged_paged_attention_pallas(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=5,
        scale=scale, logit_softcap=softcap, interpret=True,
    )
    _assert_parity(out, ref, q_lens, q.shape[1])


def test_ragged_parity_decode_rows(rng):
    """Span-1 rows (the decode degenerate case) match the decode op."""
    from distllm_tpu.ops.paged_attention import (
        decode_attention,
        paged_attention_xla,
    )

    q, k, v, bt, ctx, pos, _ = _setup(rng, s=1)
    qd = q[:, 0]
    for window in (None, 6):
        ref = paged_attention_xla(
            qd, k, v, bt, ctx, sliding_window=window
        )
        out = decode_attention(
            qd, k, v, bt, ctx, ctx - 1, sliding_window=window,
            backend='interpret',
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-4
        )


def test_ragged_parity_query_tiling_and_chunking(rng):
    """Long spans across multiple query tiles and multi-page KV chunks:
    tiling must be invisible (same values as the untiled XLA gather)."""
    q, k, v, bt, ctx, pos, q_lens = _setup(
        rng, s=13, nh=8, nkv=2, num_blocks=16
    )
    ctx = jnp.asarray([30, 13, 22], jnp.int32)
    q_lens = jnp.asarray([13, 13, 7], jnp.int32)
    pos = (ctx - q_lens)[:, None] + jnp.arange(13)[None, :]
    for window in (None, 5):
        ref = ragged_paged_attention_xla(
            q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window
        )
        out = ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window,
            span_tile=4, pages_per_chunk=2, interpret=True,
        )
        _assert_parity(out, ref, q_lens, 13)


def test_ragged_pad_rows_are_exact_zeros(rng):
    """q_lens=0 rows and pad queries emit exact finite zeros — stricter
    than the XLA twin's key-0 garbage, and the property that keeps a NaN
    out of the trash block under sliding windows."""
    q, k, v, bt, ctx, pos, _ = _setup(rng, s=6)
    q_lens = jnp.asarray([6, 0, 2], jnp.int32)
    out = np.asarray(
        ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=2,
            interpret=True,
        )
    )
    assert np.isfinite(out).all()
    assert np.abs(out[1]).max() == 0.0  # fully padded row
    assert np.abs(out[2, 2:]).max() == 0.0  # pad tail of a ragged row


def test_ragged_q_lens_none_matches_xla(rng):
    """q_lens=None: every span position is computed as a live query (the
    prefill alias contract) — full-tensor parity, not just valid rows."""
    q, k, v, bt, ctx, pos, _ = _setup(rng)
    ctx = jnp.asarray([17, 9, 12], jnp.int32)
    pos = (ctx - q.shape[1])[:, None] + jnp.arange(q.shape[1])[None, :]
    ref = ragged_paged_attention_xla(q, k, v, bt, ctx, pos, q_lens=None)
    out = ragged_paged_attention_pallas(
        q, k, v, bt, ctx, pos, q_lens=None, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-4
    )


def test_dispatcher_backend_routing(rng):
    """The one serving callsite: 'xla' and 'interpret' agree on valid
    rows; an unresolved selector fails loudly."""
    from distllm_tpu.ops.paged_attention import ragged_paged_attention

    q, k, v, bt, ctx, pos, q_lens = _setup(rng)
    ref = ragged_paged_attention(
        q, k, v, bt, ctx, pos, q_lens=q_lens, backend='xla'
    )
    out = ragged_paged_attention(
        q, k, v, bt, ctx, pos, q_lens=q_lens, backend='interpret'
    )
    _assert_parity(out, ref, q_lens, q.shape[1])
    with pytest.raises(ValueError, match='attn backend'):
        ragged_paged_attention(
            q, k, v, bt, ctx, pos, q_lens=q_lens, backend='auto'
        )


def test_resolve_attn_backend_contract(monkeypatch):
    from types import SimpleNamespace

    from distllm_tpu.ops.paged_attention import resolve_attn_backend

    mc = SimpleNamespace(head_size=128)
    # CPU: 'auto' must land on the always-available XLA fallback.
    assert resolve_attn_backend('auto', mc) == 'xla'
    # Explicit pins pass through untouched.
    assert resolve_attn_backend('pallas', mc) == 'pallas'
    assert resolve_attn_backend('interpret', mc) == 'interpret'
    with pytest.raises(ValueError, match='attn_backend'):
        resolve_attn_backend('cuda', mc)
    # On TPU, 'auto' eligibility includes the kernel's DMA contract on
    # the KV block geometry: a block_size the kernel would reject must
    # resolve to XLA (never trace into the kernel's ValueError), while
    # the default geometry selects the kernel.
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    # Head-dim CI contract: 128 is tested, 256 is a multiple of 128 but
    # outside TESTED_HEAD_DIMS so 'auto' must keep XLA.
    assert resolve_attn_backend('auto', mc) == 'pallas'
    assert (
        resolve_attn_backend('auto', SimpleNamespace(head_size=256)) == 'xla'
    )
    assert resolve_attn_backend(
        'auto', mc, block_size=16, kv_dtype='bfloat16'
    ) == 'pallas'
    assert resolve_attn_backend(
        'auto', mc, block_size=8, kv_dtype='bfloat16'
    ) == 'xla'
    assert resolve_attn_backend(
        'auto', mc, block_size=8, kv_dtype='float32'
    ) == 'pallas'  # fp32 sublane tile is 8


def _tiny_engine(attn_backend):
    from distllm_tpu.generate.engine import EngineConfig, LLMEngine
    from distllm_tpu.models import mistral

    cfg = mistral.MistralConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)

    class _Tok:
        eos_id = None

    engine_cfg = EngineConfig(
        block_size=4, num_blocks=48, max_num_seqs=3, max_model_len=64,
        decode_steps=4, pipeline_depth=1, attn_backend=attn_backend,
        enable_prefix_cache=True, prefill_chunk_tokens=8,
    )
    return LLMEngine(cfg, params, _Tok(), engine_cfg)


@pytest.mark.parametrize('flipped', ['interpret'])
def test_engine_token_identity_backend_flipped(flipped):
    """Greedy fp32 serving produces IDENTICAL tokens with the backend
    flipped from 'xla' to the ragged Pallas kernel (interpret mode — the
    same kernel the TPU compiles). Prefix cache + chunked prefill are on,
    so both ragged chunk spans and span-1 decode rows dispatch through
    the flipped kernel. This is the engine-level identity boundary from
    docs/serving.md: cross-kernel identity is pinned in fp32 (bf16 may
    round a near-tied logit differently across compiled programs)."""
    from distllm_tpu.generate.engine import SamplingParams

    rng = np.random.default_rng(7)
    shared = list(rng.integers(1, 128, size=10))
    prompts = [
        shared + list(rng.integers(1, 128, size=int(n)))
        for n in (3, 11, 6)
    ]
    sampling = SamplingParams(temperature=0.0, max_tokens=6)
    outs = {}
    for backend in ('xla', flipped):
        engine = _tiny_engine(backend)
        assert engine.telemetry['attn_backend'] == backend
        outs[backend] = engine.generate_ids(prompts, sampling)
        engine.shutdown()
    assert outs['xla'] == outs[flipped], (
        'greedy fp32 token stream diverged when the attention backend '
        'flipped — the kernel identity contract is broken'
    )
