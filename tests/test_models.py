"""Model numerics parity vs HuggingFace torch (tiny local checkpoints).

No network: tiny random-init HF models are constructed in-process, their
state dicts converted with ``params_from_hf``, and JAX forwards compared to
the torch reference in float32.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distllm_tpu.models import bert as jbert
from distllm_tpu.models import esm2 as jesm
from distllm_tpu.models import mistral as jmistral

torch = pytest.importorskip('torch')


def _to_numpy_state(model):
    return {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}


def _rand_batch(rng, batch, seq, vocab, pad_from=None):
    ids = rng.integers(4, vocab, size=(batch, seq)).astype(np.int32)
    mask = np.ones((batch, seq), np.int32)
    if pad_from is not None:
        for row, start in enumerate(pad_from):
            mask[row, start:] = 0
            ids[row, start:] = 0
    return ids, mask


@pytest.fixture(scope='module')
def np_rng():
    return np.random.default_rng(42)


def test_bert_matches_hf(np_rng):
    from transformers import BertConfig, BertModel

    hf_cfg = BertConfig(
        vocab_size=97,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=64,
        max_position_embeddings=48,
        type_vocab_size=2,
    )
    model = BertModel(hf_cfg).eval()
    cfg = jbert.BertConfig.from_hf_config(hf_cfg.to_dict())
    cfg.dtype = 'float32'
    params = jbert.params_from_hf(_to_numpy_state(model), cfg)

    ids, mask = _rand_batch(np_rng, 3, 16, 97, pad_from=[16, 12, 9])
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(ids.astype(np.int64)),
            attention_mask=torch.tensor(mask.astype(np.int64)),
        ).last_hidden_state.numpy()
    ours = np.asarray(jbert.apply(params, cfg, ids, mask))
    # Compare only unpadded positions (padding rows diverge harmlessly).
    valid = mask.astype(bool)
    np.testing.assert_allclose(ours[valid], ref[valid], atol=2e-5, rtol=1e-4)


def test_mistral_matches_hf(np_rng):
    from transformers import MistralConfig, MistralModel

    hf_cfg = MistralConfig(
        vocab_size=101,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=64,
        rope_theta=10000.0,
        sliding_window=None,
    )
    model = MistralModel(hf_cfg).eval()
    cfg = jmistral.MistralConfig.from_hf_config(hf_cfg.to_dict())
    cfg.dtype = 'float32'
    params = jmistral.params_from_hf(_to_numpy_state(model), cfg)

    ids, mask = _rand_batch(np_rng, 2, 12, 101)
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(ids.astype(np.int64)),
            attention_mask=torch.tensor(mask.astype(np.int64)),
        ).last_hidden_state.numpy()
    ours = np.asarray(jmistral.apply(params, cfg, ids, mask))
    np.testing.assert_allclose(ours, ref, atol=3e-5, rtol=1e-4)


def test_llama3_rope_scaling_matches_hf(np_rng):
    """Llama-3 checkpoints carry rope_scaling (llama3 frequency banding);
    ignoring it mis-positions every token past the original context, so
    the scaled tables are golden-tested against transformers."""
    from transformers import LlamaConfig, LlamaModel

    rope_scaling = {
        'rope_type': 'llama3', 'factor': 8.0, 'low_freq_factor': 1.0,
        'high_freq_factor': 4.0, 'original_max_position_embeddings': 16,
    }
    hf_cfg = LlamaConfig(
        vocab_size=101, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        max_position_embeddings=128, rope_scaling=rope_scaling,
        attention_bias=False,
    )
    model = LlamaModel(hf_cfg).eval()
    cfg = jmistral.MistralConfig.from_hf_config(hf_cfg.to_dict())
    assert cfg.rope_scaling is not None
    cfg.dtype = 'float32'
    params = jmistral.params_from_hf(_to_numpy_state(model), cfg)

    # Long enough that scaled and unscaled tables genuinely differ.
    ids, mask = _rand_batch(np_rng, 2, 48, 101)
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(ids.astype(np.int64)),
            attention_mask=torch.tensor(mask.astype(np.int64)),
        ).last_hidden_state.numpy()
    ours = np.asarray(jmistral.apply(params, cfg, ids, mask))
    np.testing.assert_allclose(ours, ref, atol=5e-5, rtol=1e-4)
    # And the scaling is actually load-bearing at these lengths:
    cfg_unscaled = cfg.model_copy(update={'rope_scaling': None})
    unscaled = np.asarray(jmistral.apply(params, cfg_unscaled, ids, mask))
    assert np.abs(unscaled - ref).max() > 1e-3


def test_rope_scaling_unknown_type_raises():
    from distllm_tpu.models import common as jcommon

    with pytest.raises(NotImplementedError, match='longrope'):
        jcommon.rope_frequencies(
            64, 32, 1e4, {'rope_type': 'longrope', 'factor': 4.0}
        )


def test_qwen2_matches_hf(np_rng):
    """Qwen2 = Mistral architecture + Q/K/V biases; same module serves it
    (auto-dispatch via model_type, auto.py _FAMILIES)."""
    from transformers import Qwen2Config, Qwen2Model

    hf_cfg = Qwen2Config(
        vocab_size=101,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=64,
        max_position_embeddings=64,
        rope_theta=10000.0,
        use_sliding_window=False,
    )
    model = Qwen2Model(hf_cfg).eval()
    hf_dict = hf_cfg.to_dict()
    cfg = jmistral.MistralConfig.from_hf_config(hf_dict)
    assert cfg.attention_bias  # inferred from model_type == 'qwen2'
    # use_sliding_window=False must win over the sliding_window value the
    # Qwen2 config carries anyway.
    assert cfg.sliding_window is None
    cfg.dtype = 'float32'
    params = jmistral.params_from_hf(_to_numpy_state(model), cfg)
    assert 'bias' in params['layers']['q']

    ids, mask = _rand_batch(np_rng, 2, 12, 101)
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(ids.astype(np.int64)),
            attention_mask=torch.tensor(mask.astype(np.int64)),
        ).last_hidden_state.numpy()
    ours = np.asarray(jmistral.apply(params, cfg, ids, mask))
    np.testing.assert_allclose(ours, ref, atol=3e-5, rtol=1e-4)


def test_qwen2_decode_matches_prefill(np_rng):
    """The biased projections must flow through the paged decode path too:
    greedy decode_step logits == prefill logits at the same position."""
    cfg = jmistral.MistralConfig(
        vocab_size=64, hidden_size=16, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=32, attention_bias=True,
        dtype='float32',
    )
    params = jmistral.init(jax.random.PRNGKey(0), cfg)
    ids, mask = _rand_batch(np_rng, 1, 6, 64)
    hidden, k, v = jmistral.prefill(params, cfg, ids, mask)
    want = np.asarray(jmistral.logits(params, cfg, hidden))[0, -1]

    from distllm_tpu.generate.engine.engine import _write_prefill_all_layers
    from distllm_tpu.ops.paged_attention import fold_heads

    bs, nb = 4, 8
    kshape = (cfg.num_layers, nb, bs, cfg.num_kv_heads * cfg.head_size)
    k_cache = jnp.zeros(kshape, jnp.float32)
    v_cache = jnp.zeros(kshape, jnp.float32)
    table = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    k_cache, v_cache = _write_prefill_all_layers(
        k_cache, v_cache, fold_heads(k), fold_heads(v), table,
        jnp.asarray([6], jnp.int32)
    )
    lg, _, _ = jmistral.decode_step(
        params, cfg, jnp.asarray(ids[:, -1]), jnp.asarray([5], jnp.int32),
        k_cache, v_cache, table, jnp.asarray([6], jnp.int32),
    )
    np.testing.assert_allclose(np.asarray(lg)[0], want, atol=2e-5)


def test_mistral_logits_and_prefill(np_rng):
    cfg = jmistral.MistralConfig(
        vocab_size=64,
        hidden_size=16,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=32,
        dtype='float32',
    )
    params = jmistral.init(jax.random.PRNGKey(0), cfg)
    ids, mask = _rand_batch(np_rng, 2, 8, 64)
    hidden, k, v = jmistral.prefill(params, cfg, ids, mask)
    assert hidden.shape == (2, 8, 16)
    assert k.shape == (cfg.num_layers, 2, 8, cfg.num_kv_heads, cfg.head_size)
    lg = jmistral.logits(params, cfg, hidden)
    assert lg.shape == (2, 8, 64)
    assert lg.dtype == np.float32


def test_esm2_matches_hf(np_rng):
    from transformers import EsmConfig, EsmModel

    hf_cfg = EsmConfig(
        vocab_size=33,
        hidden_size=24,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=48,
        max_position_embeddings=128,
        position_embedding_type='rotary',
        token_dropout=True,
        mask_token_id=32,
        pad_token_id=1,
        emb_layer_norm_before=False,
    )
    model = EsmModel(hf_cfg, add_pooling_layer=False).eval()
    cfg = jesm.Esm2Config.from_hf_config(hf_cfg.to_dict())
    cfg.dtype = 'float32'
    params = jesm.params_from_hf(_to_numpy_state(model), cfg)

    ids, mask = _rand_batch(np_rng, 2, 10, 30, pad_from=[10, 7])
    ids[mask == 0] = 1  # ESM pad token
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(ids.astype(np.int64)),
            attention_mask=torch.tensor(mask.astype(np.int64)),
        ).last_hidden_state.numpy()
    ours = np.asarray(jesm.apply(params, cfg, ids, mask))
    valid = mask.astype(bool)
    np.testing.assert_allclose(ours[valid], ref[valid], atol=3e-5, rtol=1e-4)


def test_bert_tp_sharding_matches_single_device():
    """TP over the 8-device virtual mesh == single-device numerics."""
    from distllm_tpu.parallel import make_mesh, shard_pytree
    from distllm_tpu.parallel.mesh import MeshSpec

    cfg = jbert.BertConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        intermediate_size=64,
        max_position_embeddings=32,
        dtype='float32',
    )
    params = jbert.init(jax.random.PRNGKey(1), cfg)
    ids = np.arange(2 * 16).reshape(2, 16).astype(np.int32) % 64
    mask = np.ones((2, 16), np.int32)
    expected = np.asarray(jbert.apply(params, cfg, ids, mask))

    mesh = make_mesh(MeshSpec(data=2, model=4))
    sharded = shard_pytree(params, jbert.param_specs(cfg), mesh)
    fn = jax.jit(lambda p, i, m: jbert.apply(p, cfg, i, m))
    out = np.asarray(fn(sharded, ids, mask))
    np.testing.assert_allclose(out, expected, atol=1e-5, rtol=1e-5)


def test_mixtral_moe_mlp_matches_expert_loop(np_rng):
    """Dense-einsum routed MoE == explicit per-expert loop (fp32)."""
    from distllm_tpu.models import mixtral as jmix

    b, s, h, i, e, k = 2, 6, 16, 32, 4, 2
    r = np_rng
    x = r.standard_normal((b, s, h)).astype(np.float32)
    router = r.standard_normal((h, e)).astype(np.float32) * 0.1
    gate = r.standard_normal((e, h, i)).astype(np.float32) * 0.1
    up = r.standard_normal((e, h, i)).astype(np.float32) * 0.1
    down = r.standard_normal((e, i, h)).astype(np.float32) * 0.1

    out = np.asarray(jmix.moe_mlp(x, router, gate, up, down, k))

    # reference: loop over tokens and their top-k experts
    import scipy.special as sp

    probs = sp.softmax(x.reshape(-1, h) @ router, axis=-1)
    expected = np.zeros((b * s, h), np.float32)
    for t, row in enumerate(x.reshape(-1, h)):
        idx = np.argsort(-probs[t])[:k]
        w = probs[t, idx] / probs[t, idx].sum()
        for j, ei in enumerate(idx):
            hid = (row @ gate[ei]) * sp.expit(row @ gate[ei]) * (row @ up[ei])
            expected[t] += w[j] * (hid @ down[ei])
    np.testing.assert_allclose(
        out.reshape(-1, h), expected, atol=1e-4, rtol=1e-4
    )


def test_mixtral_matches_hf(np_rng):
    from transformers import MixtralConfig as HFMixtralConfig
    from transformers import MixtralModel

    hf_cfg = HFMixtralConfig(
        vocab_size=89,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        intermediate_size=48,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=64,
        rope_theta=10000.0,
        sliding_window=None,
    )
    model = MixtralModel(hf_cfg).eval()
    from distllm_tpu.models import mixtral as jmix

    cfg = jmix.MixtralConfig.from_hf_config(hf_cfg.to_dict())
    cfg.dtype = 'float32'
    params = jmix.params_from_hf(_to_numpy_state(model), cfg)

    ids, mask = _rand_batch(np_rng, 2, 10, 89)
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(ids.astype(np.int64)),
            attention_mask=torch.tensor(mask.astype(np.int64)),
        ).last_hidden_state.numpy()
    ours = np.asarray(jmix.apply(params, cfg, ids, mask))
    np.testing.assert_allclose(ours, ref, atol=5e-5, rtol=1e-4)


def test_mixtral_serving_decode_matches_apply(np_rng):
    """Mixtral must flow through the shared paged serving machinery: the
    engine-facing prefill + greedy decode_step reproduce apply()'s
    next-token logits (MoE routing inside the decode layer loop)."""
    from distllm_tpu.generate.engine.engine import _write_prefill_all_layers
    from distllm_tpu.ops.paged_attention import fold_heads
    from distllm_tpu.models import mixtral as jmix

    cfg = jmix.MixtralConfig(
        vocab_size=64, hidden_size=16, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=32, num_experts=4,
        experts_per_token=2, dtype='float32',
    )
    params = jmix.init(jax.random.PRNGKey(0), cfg)
    ids, mask = _rand_batch(np_rng, 1, 6, 64)
    hidden, k, v = jmix.prefill(params, cfg, ids, mask)
    # prefill's hidden must agree with the family's own apply().
    np.testing.assert_allclose(
        np.asarray(hidden), np.asarray(jmix.apply(params, cfg, ids, mask)),
        atol=1e-5,
    )
    want = np.asarray(jmix.logits(params, cfg, hidden))[0, -1]

    bs, nb = 4, 8
    kshape = (cfg.num_layers, nb, bs, cfg.num_kv_heads * cfg.head_size)
    k_cache = jnp.zeros(kshape, jnp.float32)
    v_cache = jnp.zeros(kshape, jnp.float32)
    table = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    k_cache, v_cache = _write_prefill_all_layers(
        k_cache, v_cache, fold_heads(k), fold_heads(v), table,
        jnp.asarray([6], jnp.int32)
    )
    lg, _, _ = jmix.decode_step(
        params, cfg, jnp.asarray(ids[:, -1]), jnp.asarray([5], jnp.int32),
        jnp.array(k_cache), jnp.array(v_cache), table,
        jnp.asarray([6], jnp.int32),
    )
    np.testing.assert_allclose(np.asarray(lg)[0], want, atol=2e-5)
    # And the full engine serves it end to end.
    from distllm_tpu.generate.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )

    class _Tok:
        eos_id = None

    engine = LLMEngine(
        cfg, params, _Tok(),
        EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2,
                     max_model_len=32, prefill_min_bucket=8),
    )
    outs = engine.generate_ids(
        [[5, 9, 17], [3, 20]], SamplingParams(temperature=0.0, max_tokens=4)
    )
    engine.shutdown()
    assert all(len(o) == 4 for o in outs), outs


def test_mixtral_int8_serving(np_rng):
    """Weight-only int8 covers the 4-D expert banks (the bulk of an MoE
    model); the quantized engine must serve, and quantized decode logits
    must sit near the float ones (per-expert-channel scales)."""
    from distllm_tpu.generate.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distllm_tpu.models import mixtral as jmix
    from distllm_tpu.ops.quantization import QTensor, quantize_pytree

    cfg = jmix.MixtralConfig(
        vocab_size=64, hidden_size=16, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=32, num_experts=4,
        experts_per_token=2, dtype='float32',
    )
    params = jmix.init(jax.random.PRNGKey(1), cfg)
    qparams = quantize_pytree(params, mode='int8', min_size=1)
    assert isinstance(qparams['layers']['gate']['kernel'], QTensor)

    ids, mask = _rand_batch(np_rng, 1, 5, 64)
    want = np.asarray(
        jmix.logits(params, cfg, jmix.apply(params, cfg, ids, mask))
    )[0, -1]
    got = np.asarray(
        jmix.logits(qparams, cfg, jmix.apply(qparams, cfg, ids, mask))
    )[0, -1]
    # int8 error is small but nonzero; the distributions must stay close.
    assert np.abs(got - want).max() < 0.05

    class _Tok:
        eos_id = None

    engine = LLMEngine(
        cfg, qparams, _Tok(),
        EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2,
                     max_model_len=32, prefill_min_bucket=8),
    )
    outs = engine.generate_ids(
        [[5, 9, 17]], SamplingParams(temperature=0.0, max_tokens=4)
    )
    engine.shutdown()
    assert len(outs[0]) == 4


def test_mixtral_ep_sharding_matches_single_device():
    """EP x TP over the 8-device mesh == single-device numerics."""
    from distllm_tpu.models import mixtral as jmix
    from distllm_tpu.parallel import make_mesh, shard_pytree
    from distllm_tpu.parallel.mesh import MeshSpec

    cfg = jmix.MixtralConfig(
        vocab_size=64,
        hidden_size=16,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=32,
        num_experts=4,
        experts_per_token=2,
        dtype='float32',
    )
    params = jmix.init(jax.random.PRNGKey(2), cfg)
    ids = np.arange(2 * 8).reshape(2, 8).astype(np.int32) % 64
    mask = np.ones((2, 8), np.int32)
    expected = np.asarray(jmix.apply(params, cfg, ids, mask))

    mesh = make_mesh(MeshSpec(data=1, seq=1, expert=4, model=2))
    sharded = shard_pytree(params, jmix.param_specs(cfg, params), mesh)
    fn = jax.jit(lambda p, i, m: jmix.apply(p, cfg, i, m))
    out = np.asarray(fn(sharded, ids, mask))
    np.testing.assert_allclose(out, expected, atol=1e-5, rtol=1e-5)


def test_gemma_matches_hf(np_rng):
    """Gemma-1: GeGLU, sqrt(hidden) embedding scale, (1+w) RMSNorm, tied
    embeddings — all config knobs on the shared family forward."""
    from transformers import GemmaConfig, GemmaModel

    from distllm_tpu.models import gemma as jgemma

    hf_cfg = GemmaConfig(
        vocab_size=101, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=64, max_position_embeddings=64,
        hidden_act='gelu_pytorch_tanh', rms_norm_eps=1e-6,
    )
    model = GemmaModel(hf_cfg).eval()
    cfg = jgemma.GemmaConfig.from_hf_config(hf_cfg.to_dict())
    assert cfg.norm_plus_one and cfg.embedding_multiplier is not None
    cfg.dtype = 'float32'
    params = jgemma.params_from_hf(_to_numpy_state(model), cfg)

    ids, mask = _rand_batch(np_rng, 2, 12, 101)
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(ids.astype(np.int64)),
            attention_mask=torch.tensor(mask.astype(np.int64)),
        ).last_hidden_state.numpy()
    ours = np.asarray(jgemma.apply(params, cfg, ids, mask))
    np.testing.assert_allclose(ours, ref, atol=3e-5, rtol=1e-4)


def test_gemma2_matches_hf(np_rng):
    """Gemma-2 adds sandwich norms, logit softcaps, query_pre_attn scaling
    and the alternating local/global window pattern; golden against HF
    incl. a sequence LONGER than the sliding window so the per-layer
    window masks are load-bearing."""
    from transformers import Gemma2Config, Gemma2ForCausalLM

    from distllm_tpu.models import gemma as jgemma

    hf_cfg = Gemma2Config(
        vocab_size=101, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=64, max_position_embeddings=96,
        hidden_activation='gelu_pytorch_tanh', rms_norm_eps=1e-6,
        query_pre_attn_scalar=16, sliding_window=8,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        attn_implementation='eager',  # softcap path; sdpa impl drops it
    )
    model = Gemma2ForCausalLM(hf_cfg).eval()
    cfg = jgemma.GemmaConfig.from_hf_config(hf_cfg.to_dict())
    assert cfg.post_norms and cfg.sliding_window_pattern == 'alternating'
    assert cfg.attn_logit_softcap == 50.0
    cfg.dtype = 'float32'
    params = jgemma.params_from_hf(_to_numpy_state(model), cfg)

    # seq 24 > window 8: window masks matter on the even (local) layers.
    ids, mask = _rand_batch(np_rng, 2, 24, 101)
    with torch.no_grad():
        ref = model(
            input_ids=torch.tensor(ids.astype(np.int64)),
            attention_mask=torch.tensor(mask.astype(np.int64)),
        ).logits.numpy()
    hidden = np.asarray(jgemma.apply(params, cfg, ids, mask))
    ours = np.asarray(jgemma.logits(params, cfg, hidden))
    np.testing.assert_allclose(ours, ref, atol=5e-5, rtol=1e-4)
    # The alternating pattern is load-bearing: all-global diverges.
    cfg_glob = cfg.model_copy(update={'sliding_window': None,
                                      'sliding_window_pattern': 'all'})
    glob_hidden = np.asarray(jgemma.apply(params, cfg_glob, ids, mask))
    assert np.abs(glob_hidden - hidden).max() > 1e-4
