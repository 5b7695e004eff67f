#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that distllm-tpu still starts on the chip.

One process, one TPU v5e chip, no child that touches JAX. Every weight and
every text is made from ``--seed``; nothing is downloaded. Phases, each
printed as one JSON line (``{"phase": ..., "ok": ...}``):

- ``device``  — ``jax.devices()`` must be a TPU whose kind is in the peaks
  table; prints the versions and the compile-cache directory in use;
- ``kernels`` — every Pallas kernel of the main path, compiled (never
  interpreted) at the real widths, against its XLA twin on the device;
- ``embed``   — ``distllm_tpu.distributed_embedding`` driven the way its
  ``main`` drives it, PubMedBERT widths and depth, against the same model
  on the XLA attention path in float32;
- ``hybrid``  — one ``granitemoehybrid`` request pair through
  ``LLMEngine.generate_ids`` at the widths of
  ``benchmarks/configs/granite-4.0-h-small.json``: the prompts admitted
  whole, the long one prefilled in two spans, the state pool's bytes, the
  KV pool over the one attention layer, the share of routed pairs held;
- ``windowed`` — a toy ``laguna`` (head dim 128, 6 and 8 queries a KV head,
  window 64) through ``LLMEngine.generate_ids``: a prompt prefilled past
  window + chunk, decode with the window group freeing behind itself, one
  preemption and re-admission, the tokens against the plain reference;
- ``latent``  — a toy ``deepseek_v3`` with the published latent widths (a
  512-wide latent and a 64-wide rope key a token, 32 query heads on the one
  KV head) through ``LLMEngine.generate_ids``: a pool of one plane a layer,
  a prompt prefilled past two chunks, decode, one preemption and
  re-admission, the tokens against the plain reference, and the paged
  kernel against its XLA twin on a latent plane;
- ``serve``   — the OpenAI-compatible server from ``chat_server.build_app``
  on a local port, engine made by ``TpuGenerator`` with the settings of
  ``examples/chat/chat_server.rag.yaml`` at Mistral-7B-Instruct-v0.3
  widths, then asserts on the engine: every prompt admitted whole (one
  near ``max_model_len``, prefilled in chunks), a second prompt that hits
  the first one's prefix and prefills its own tail, Pallas attention, no
  ``*_fallback`` telemetry, native scheduler.

``--chips 4`` runs ONLY the across-chips phases and what they are compared
with: ``tp4`` (``tensor_parallel_size: 4`` against the one-chip engine:
weights checksum-equal and a quarter on each device, logits within the
calibrated bf16 noise) and ``index4`` (``TpuIndexV2`` with ``mesh: {data:
-1}`` against the unsharded index).

Any phase that fails makes the exit code non-zero and suppresses the last
line, which on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script prints "no TPU" and exits 2.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import gc
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
# Checkpoints, corpora and outputs of one run (14.5 GB at 7B widths): on the
# checkout's own disk, never a RAM-backed temp dir. Listed in .gitignore,
# removed when the run ends.
WORK = REPO / '.chip_smoke_work'

# Mistral-7B-Instruct-v0.3 (config.json of the published checkpoint).
MISTRAL_7B = {
    'model_type': 'mistral',
    'vocab_size': 32768,
    'hidden_size': 4096,
    'num_hidden_layers': 32,
    'num_attention_heads': 32,
    'num_key_value_heads': 8,
    'head_dim': 128,
    'intermediate_size': 14336,
    'max_position_embeddings': 32768,
    'rope_theta': 1000000.0,
    'rms_norm_eps': 1e-05,
    'sliding_window': None,
    'tie_word_embeddings': False,
    'torch_dtype': 'bfloat16',
}
# PubMedBERT (microsoft/BiomedNLP-BiomedBERT-base-uncased-abstract).
PUBMEDBERT = {
    'model_type': 'bert',
    'vocab_size': 30522,
    'hidden_size': 768,
    'num_hidden_layers': 12,
    'num_attention_heads': 12,
    'intermediate_size': 3072,
    'max_position_embeddings': 512,
    'type_vocab_size': 2,
    'layer_norm_eps': 1e-12,
    'hidden_act': 'gelu',
}


# The run's sizes. A tiny-size CPU rehearsal patches these (and the two
# configs above) from a scratch script; the committed script has one path.
EMBED_CHUNKS = 3072
EMBED_BATCH = 64
EMBED_WORDS = (120, 260)
MAX_TOKENS = 128  # the example's 1024 would be most of the time limit
KERNEL_BATCH = 32
KERNEL_BLOCKS = 1024
KERNEL_CTX = 512
ENCODER_SHAPES = ((64, 256), (64, 160))  # (batch, sequence)
INDEX_ROWS = 1_000_000
INDEX_QUERIES = 32
TP_STEPS = 8


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


_START = time.perf_counter()


def note(message: str) -> None:
    """Progress on stderr, so a phase that dies or runs long says where."""
    print(f'[chip_smoke +{time.perf_counter() - _START:.0f}s] {message}',
          file=sys.stderr, flush=True)


def run_phase(name: str, fn, *args) -> bool:
    note(f'phase {name} starts')
    start = time.perf_counter()
    try:
        fields = fn(*args) or {}
        ok = True
    except Exception as exc:
        traceback.print_exc(file=sys.stderr)
        fields = {'error': f'{type(exc).__name__}: {exc}'[:2000]}
        ok = False
    emit({
        'phase': name, 'ok': ok,
        'seconds': round(time.perf_counter() - start, 1), **fields,
    })
    return ok


# ------------------------------------------------------------------ device


def phase_device(cache_dir: str) -> dict:
    import jax
    import jaxlib

    from distllm_tpu.observability.roofline import device_peaks

    device = jax.devices()[0]
    peak_flops, peak_bw = device_peaks(device)  # raises on an unknown kind
    return {
        'platform': device.platform,
        'device_kind': device.device_kind,
        'count': len(jax.devices()),
        'jax': jax.__version__,
        'jaxlib': jaxlib.__version__,
        'libtpu': device.client.platform_version.replace('\n', ' ')[:200],
        'peak_flops': peak_flops,
        'peak_hbm_bytes_per_s': peak_bw,
        'compile_cache_dir': cache_dir,
        'compiled_so_far': compile_counts(),
    }


def compile_counts() -> dict:
    """What the process has compiled or loaded so far, by jax's own cache
    events (``CompileWatcher.summary``): programs, those the persistent
    cache gave back to XLA for a second or more, and the seconds of
    loading hits and of compiling the rest."""
    from distllm_tpu.observability.startup import get_compile_watcher

    summary = get_compile_watcher().summary()
    return {
        'programs': summary['programs'],
        'cache_miss_programs': summary['cache_miss_programs'],
        'cache_load_s': round(summary['cache_load_s'], 1),
        'compile_miss_s': round(summary['compile_miss_s'], 1),
    }


# ----------------------------------------------------------------- kernels

# Stated bf16 tolerance of a kernel against its XLA twin: both accumulate
# in fp32 and round the output once to bf16 (8 bits of mantissa, 2^-8 =
# 0.0039 relative), and the probabilities are rounded to bf16 before the
# PV product on one side or the other.
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2


def _max_err(out, ref, valid=None) -> tuple[float, bool]:
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if valid is not None:
        out, ref = out[valid], ref[valid]
    finite = bool(np.isfinite(out).all())
    err = np.abs(out - ref)
    within = bool((err <= KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)).all())
    return float(err.max()), finite and within


def phase_kernels(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distllm_tpu.ops.encoder_attention import (
        encoder_attention,
        encoder_attention_reference,
    )
    from distllm_tpu.ops.paged_attention import (
        QuantizedKV,
        ragged_paged_attention_pallas,
        ragged_paged_attention_xla,
    )

    m = MISTRAL_7B
    nh, nkv, hd = (
        m['num_attention_heads'], m['num_key_value_heads'], m['head_dim']
    )
    b, nb = KERNEL_BATCH, KERNEL_BLOCKS
    rng = np.random.default_rng(seed)
    cases = {}
    for kv_name, block_size in (('bf16', 16), ('int8', 32)):
        max_blocks = KERNEL_CTX // block_size
        shape = (nb, block_size, nkv * hd)  # head-folded, as the pool stores it
        if kv_name == 'bf16':
            k_cache = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
            v_cache = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        else:
            k_cache, v_cache = (
                QuantizedKV(
                    jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8),
                    jnp.asarray(
                        rng.uniform(0.005, 0.02, size=(nb, nkv)), jnp.float32
                    ),
                )
                for _ in range(2)
            )
        # Block 0 is the engine's trash block; tables point at scattered
        # real blocks like the allocator produces.
        tables = jnp.asarray(
            rng.integers(1, nb, size=(b, max_blocks)), jnp.int32
        )
        for span in (1, 16):
            ctx = jnp.asarray(
                rng.integers(span, KERNEL_CTX + 1, size=(b,)), jnp.int32
            )
            # Ragged rows: every other row carries fewer live queries.
            q_lens = jnp.asarray(
                np.where(np.arange(b) % 2, span, max(1, span // 2)), jnp.int32
            )
            pos = (ctx - q_lens)[:, None] + jnp.arange(span)[None, :]
            q = jnp.asarray(rng.normal(size=(b, span, nh, hd)), jnp.bfloat16)
            kernel = jax.jit(
                lambda q, k, v, bt, c, p, ql: ragged_paged_attention_pallas(
                    q, k, v, bt, c, p, q_lens=ql
                )
            )
            twin = jax.jit(
                lambda q, k, v, bt, c, p, ql: ragged_paged_attention_xla(
                    q, k, v, bt, c, p, q_lens=ql
                )
            )
            operands = (q, k_cache, v_cache, tables, ctx, pos, q_lens)
            check(
                'tpu_custom_call' in kernel.lower(*operands).compile()
                .as_text(),
                f'ragged {kv_name} span {span}: no tpu_custom_call',
            )
            valid = np.arange(span)[None, :] < np.asarray(q_lens)[:, None]
            err, ok = _max_err(kernel(*operands), twin(*operands), valid)
            cases[f'ragged_{kv_name}_block{block_size}_span{span}'] = err
            check(ok, f'ragged {kv_name} span {span}: max abs err {err}')

    hidden, heads = PUBMEDBERT['hidden_size'], PUBMEDBERT['num_attention_heads']
    for eb, es in ENCODER_SHAPES:
        q, k, v = (
            jnp.asarray(rng.normal(size=(eb, es, hidden)), jnp.bfloat16)
            for _ in range(3)
        )
        lens = rng.integers(es // 2, es + 1, size=(eb,))
        mask = jnp.asarray(np.arange(es)[None, :] < lens[:, None], jnp.int32)
        kernel = jax.jit(
            lambda q, k, v, m: encoder_attention(
                q, k, v, m, num_heads=heads
            )
        )
        twin = jax.jit(
            lambda q, k, v, m: encoder_attention_reference(
                q, k, v, m, num_heads=heads
            )
        )
        check(
            'tpu_custom_call' in kernel.lower(q, k, v, mask).compile()
            .as_text(),
            f'encoder S={es}: no tpu_custom_call',
        )
        valid = np.asarray(mask, bool)  # pad QUERY rows are discarded too
        err, ok = _max_err(kernel(q, k, v, mask), twin(q, k, v, mask), valid)
        cases[f'encoder_b{eb}_s{es}_d{hidden}'] = err
        check(ok, f'encoder attention S={es}: max abs err {err}')
    return {
        'atol': KERNEL_ATOL, 'rtol': KERNEL_RTOL, 'max_abs_err': cases,
        'tpu_custom_call': True,
    }


# ------------------------------------------------------- seed-made inputs


def write_tokenizer(model_dir: Path, vocab_size: int, max_length: int) -> None:
    """WordLevel ``tokenizer.json`` whose vocabulary covers every id the
    model can emit, so any sampled token decodes to a word."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {'[UNK]': 0, '[PAD]': 1}
    vocab.update({f'w{i}': i for i in range(2, vocab_size)})
    tok = Tokenizer(WordLevel(vocab, unk_token='[UNK]'))
    tok.pre_tokenizer = Whitespace()
    tok.save(str(model_dir / 'tokenizer.json'))
    (model_dir / 'tokenizer_config.json').write_text(json.dumps({
        'tokenizer_class': 'PreTrainedTokenizerFast',
        'pad_token': '[PAD]', 'unk_token': '[UNK]',
        'model_max_length': max_length,
    }))


def make_words(rng, count: int, vocab_size: int) -> str:
    return ' '.join(f'w{i}' for i in rng.integers(2, vocab_size, size=count))


def _normal_bf16(key, shape, scale=0.02):
    import jax
    import jax.numpy as jnp

    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        jnp.bfloat16
    )


def write_bert_checkpoint(model_dir: Path, hf: dict, seed: int) -> None:
    import jax
    import ml_dtypes
    import numpy as np

    from distllm_tpu.models.loader import save_checkpoint

    model_dir.mkdir(parents=True, exist_ok=True)
    (model_dir / 'config.json').write_text(json.dumps(hf))
    h, inter = hf['hidden_size'], hf['intermediate_size']
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 1024))
    bf16 = ml_dtypes.bfloat16

    def normal(*shape):
        return np.asarray(_normal_bf16(next(keys), shape))

    def ln(prefix):
        return {
            f'{prefix}.weight': np.ones((h,), bf16),
            f'{prefix}.bias': np.zeros((h,), bf16),
        }

    def lin(prefix, out_dim, in_dim):
        return {
            f'{prefix}.weight': normal(out_dim, in_dim),
            f'{prefix}.bias': normal(out_dim),
        }

    state = {
        'embeddings.word_embeddings.weight': normal(hf['vocab_size'], h),
        'embeddings.position_embeddings.weight': normal(
            hf['max_position_embeddings'], h
        ),
        'embeddings.token_type_embeddings.weight': normal(
            hf['type_vocab_size'], h
        ),
        **ln('embeddings.LayerNorm'),
    }
    for i in range(hf['num_hidden_layers']):
        p = f'encoder.layer.{i}'
        state.update(lin(f'{p}.attention.self.query', h, h))
        state.update(lin(f'{p}.attention.self.key', h, h))
        state.update(lin(f'{p}.attention.self.value', h, h))
        state.update(lin(f'{p}.attention.output.dense', h, h))
        state.update(ln(f'{p}.attention.output.LayerNorm'))
        state.update(lin(f'{p}.intermediate.dense', inter, h))
        state.update(lin(f'{p}.output.dense', h, inter))
        state.update(ln(f'{p}.output.LayerNorm'))
    save_checkpoint(state, model_dir)
    write_tokenizer(
        model_dir, hf['vocab_size'], hf['max_position_embeddings']
    )


def write_mistral_checkpoint(model_dir: Path, hf: dict, seed: int) -> float:
    """HF-named bf16 safetensors, one shard per layer, made on the device
    from the seed (14.5 GB at 32 layers). Returns the GB written."""
    import jax
    import ml_dtypes
    import numpy as np
    from safetensors.numpy import save_file

    model_dir.mkdir(parents=True, exist_ok=True)
    (model_dir / 'config.json').write_text(json.dumps(hf))
    h, inter = hf['hidden_size'], hf['intermediate_size']
    q_out = hf['num_attention_heads'] * hf['head_dim']
    kv_out = hf['num_key_value_heads'] * hf['head_dim']
    shapes = {
        'self_attn.q_proj': (q_out, h), 'self_attn.k_proj': (kv_out, h),
        'self_attn.v_proj': (kv_out, h), 'self_attn.o_proj': (h, q_out),
        'mlp.gate_proj': (inter, h), 'mlp.up_proj': (inter, h),
        'mlp.down_proj': (h, inter),
    }
    ones = np.ones((h,), ml_dtypes.bfloat16)

    @jax.jit
    def layer_weights(key):
        keys = jax.random.split(key, len(shapes))
        return {
            name: _normal_bf16(k, shape)
            for k, (name, shape) in zip(keys, shapes.items())
        }

    root = jax.random.PRNGKey(seed)
    written = 0
    for i in range(hf['num_hidden_layers']):
        state = {
            f'model.layers.{i}.{name}.weight': np.asarray(w)
            for name, w in layer_weights(jax.random.fold_in(root, i)).items()
        }
        state[f'model.layers.{i}.input_layernorm.weight'] = ones
        state[f'model.layers.{i}.post_attention_layernorm.weight'] = ones
        save_file(state, str(model_dir / f'model-layer{i:03d}.safetensors'))
        written += sum(a.nbytes for a in state.values())
    k_embed, k_head = jax.random.split(jax.random.fold_in(root, 1 << 20))
    state = {
        'model.embed_tokens.weight': np.asarray(
            _normal_bf16(k_embed, (hf['vocab_size'], h))
        ),
        'model.norm.weight': ones,
        'lm_head.weight': np.asarray(
            _normal_bf16(k_head, (hf['vocab_size'], h))
        ),
    }
    save_file(state, str(model_dir / 'model-embed.safetensors'))
    written += sum(a.nbytes for a in state.values())
    # What a published decoder's tokenizer_config.json carries: HF's
    # "unset" sentinel, not a context length.
    write_tokenizer(model_dir, hf['vocab_size'], int(1e30))
    return written / 1e9


# ------------------------------------------------------------------- embed


def phase_embed(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distllm_tpu import distributed_embedding
    from distllm_tpu.embed import get_encoder, get_pooler
    from distllm_tpu.models import bert
    from distllm_tpu.ops.encoder_attention import resolve_use_pallas
    from distllm_tpu.registry import registry

    work = WORK / 'embed'
    model_dir = work / 'pubmedbert'
    write_bert_checkpoint(model_dir, PUBMEDBERT, seed)
    rng = np.random.default_rng(seed)
    (work / 'inputs').mkdir(parents=True)
    lo, hi = EMBED_WORDS
    with open(work / 'inputs' / 'corpus.jsonl', 'w') as fh:
        for i in range(EMBED_CHUNKS):
            words = make_words(
                rng, int(rng.integers(lo, hi + 1)), PUBMEDBERT['vocab_size']
            )
            fh.write(json.dumps({'text': words, 'path': f'doc{i}'}) + '\n')
    encoder_config = {
        'name': 'auto',
        'pretrained_model_name_or_path': str(model_dir),
        'half_precision': True,
    }
    config = distributed_embedding.Config(
        input_dir=work / 'inputs',
        output_dir=work / 'out',
        glob_patterns=['*.jsonl'],
        dataset_config={'name': 'jsonl', 'batch_size': EMBED_BATCH},
        encoder_config=encoder_config,
        pooler_config={'name': 'mean'},
        embedder_config={'name': 'full_sequence', 'normalize_embeddings': True},
        writer_config={'name': 'numpy'},
        compute_config={'name': 'local'},
    )
    start = time.perf_counter()
    check(distributed_embedding.run_embedding(config) == 0, 'driver failed')
    embed_s = time.perf_counter() - start

    shards = sorted((work / 'out' / 'embeddings').iterdir())
    check(len(shards) == 1, f'expected one output shard, found {len(shards)}')
    emb = np.load(shards[0] / 'embeddings.npy')
    texts = list(np.load(shards[0] / 'text.npy', allow_pickle=True))
    hidden = PUBMEDBERT['hidden_size']
    check(
        emb.shape == (EMBED_CHUNKS, hidden),
        f'shard shape {emb.shape}, expected {(EMBED_CHUNKS, hidden)}',
    )
    check(bool(np.isfinite(emb).all()), 'non-finite embeddings in the shard')

    # The same model on the XLA attention path in float32, a handful of
    # rows. The encoder is the driver's own (the warm-start registry hands
    # back the instance the worker built), so the weights are the ones
    # that produced the shard.
    encoder = get_encoder(encoder_config, register=True)
    pooler = get_pooler({'name': 'mean'})
    rows = [int(i) for i in rng.integers(0, EMBED_CHUNKS, size=8)]
    batch = encoder.tokenizer([texts[i] for i in rows])
    seq_len = int(batch.input_ids.shape[1])
    model_cfg = encoder.model_cfg
    attn_path = (
        'pallas'
        if resolve_use_pallas(
            'auto', seq_len, hidden, model_cfg.num_heads, model_cfg.dtype
        )
        else 'xla'
    )
    cfg32 = model_cfg.model_copy(update={'dtype': 'float32'})
    params32 = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                            encoder.params)
    hidden32 = jax.jit(
        lambda p, ids, mask: bert.apply(p, cfg32, ids, mask, attn_impl='xla')
    )(params32, batch.input_ids, batch.attention_mask)
    ref = np.asarray(pooler.pool(hidden32, batch.attention_mask), np.float32)
    ref = ref / np.linalg.norm(ref, axis=1, keepdims=True)
    cosines = [float(ref[j] @ emb[i]) for j, i in enumerate(rows)]
    check(min(cosines) >= 0.99, f'cosine vs fp32 XLA reference: {cosines}')
    check(
        attn_path == 'pallas',
        f'encoder attention ran on {attn_path!r} at S={seq_len}',
    )
    registry().clear()  # frees the encoder's HBM before the serve phase
    return {
        'shard': str(shards[0].relative_to(work)),
        'shape': list(emb.shape),
        'finite': True,
        'min_cosine_vs_fp32_xla': min(cosines),
        'rows_compared': len(rows),
        'encoder_attention': attn_path,
        'embed_seconds': round(embed_s, 1),
    }


# ------------------------------------------------------------------- serve


# ------------------------------------------------------------------ hybrid
HYBRID_CONFIG = REPO / 'benchmarks/configs/granite-4.0-h-small.json'
HYBRID_PROMPT_TOKENS = 700  # two spans: 512 and 188, state carried between
HYBRID_OUTPUT_TOKENS = 24


@contextlib.contextmanager
def _jax_cache_floor():
    """While an engine that owns its weights is built: the engine moves
    device-resident weights into the decode window's layouts with tiny
    jitted identities. One loaded back from the persistent cache comes out
    in the DEFAULT layout (jax 0.9.0; on the chip: the second of two equal
    leaves, and every leaf of the next process), and the programs then
    reject the weights. This script caches every compile; inside this
    block it keeps jax's own floor, under which those identities are never
    written, and first removes the ones an earlier run left in the cache
    (``phase_serve``'s generator migrates its weights outside this block):
    the floor stops a write, not a load."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    for entry in Path(cache_dir).glob('jit__lambda*') if cache_dir else ():
        if entry.is_dir():
            shutil.rmtree(entry, ignore_errors=True)
        else:
            entry.unlink(missing_ok=True)
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 1.0)
    try:
        yield
    finally:
        jax.config.update('jax_persistent_cache_min_compile_time_secs', floor)


def phase_hybrid(seed: int) -> dict:
    """One ``granitemoehybrid`` request through ``generate_ids`` at the
    benchmark configuration's widths: what the engine admitted, how it
    prefilled, and the state pool it holds beside the KV pool."""
    import jax
    import numpy as np

    from distllm_tpu.generate.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distllm_tpu.models import granite_hybrid

    model = json.loads(HYBRID_CONFIG.read_text())
    cfg = granite_hybrid.GraniteHybridConfig.from_hf_config(model).model_copy(
        update={'dtype': model['dtype']}
    )
    params = granite_hybrid.init_on_device(jax.random.PRNGKey(seed % 2**31), cfg)

    class NoTokenizer:
        eos_id = None

    start = time.perf_counter()
    with _jax_cache_floor():
        engine = LLMEngine(
            cfg, params, NoTokenizer(), EngineConfig(**model['engine']),
            own_params=True,
        )
    del params
    build_s = time.perf_counter() - start
    note(f'hybrid: engine built in {build_s:.0f}s')
    settings = model['engine']
    per_slot = cfg.num_mamba_layers * (
        cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state * 4
        + (cfg.mamba_d_conv - 1) * cfg.conv_dim * 2
    )
    check(
        engine.telemetry['state_pool_bytes'] == settings['max_num_seqs'] * per_slot,
        f"state pool holds {engine.telemetry['state_pool_bytes']} bytes, "
        f"{settings['max_num_seqs']} slots of {per_slot} expected",
    )
    check(engine.telemetry['attn_backend'] == 'pallas',
          f"attn_backend resolved to {engine.telemetry['attn_backend']!r}")
    check(engine.kv.shape[0] == cfg.num_paged_layers == 1,
          f'KV pool over {engine.kv.shape[0]} layers')
    rng = np.random.default_rng(seed)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, HYBRID_PROMPT_TOKENS)]
    before = engine.flight.total_recorded
    start = time.perf_counter()
    out = engine.generate_ids(
        [prompt, prompt[:40]],
        SamplingParams(temperature=0.0, max_tokens=HYBRID_OUTPUT_TOKENS),
    )
    generate_s = time.perf_counter() - start
    records = engine.flight.snapshot()[-(engine.flight.total_recorded - before):]
    requests = [r for r in records if r['kind'] == 'request']
    windows = [r for r in records if r['kind'] == 'decode']
    check([len(o) for o in out] == [HYBRID_OUTPUT_TOKENS] * 2,
          f'generated {[len(o) for o in out]} tokens')
    check(all(0 <= t < cfg.vocab_size for o in out for t in o),
          'a token outside the held vocabulary')
    # Nothing between generate_ids and the scheduler cut the prompt.
    check(sorted(r['prompt_tokens'] for r in requests) == [40, HYBRID_PROMPT_TOKENS],
          f"admitted {[r['prompt_tokens'] for r in requests]} prompt tokens")
    routes = {
        (r['route'], r['tokens']) for r in records if r['kind'] == 'prefill'
    }
    check(routes == {('chunk', 512), ('chunk', 188), ('paged', 40)},
          f'prefill dispatches {sorted(routes)}')
    pairs = sum(r['moe_pairs'] for r in windows)
    held = sum(r['moe_pairs_held'] for r in windows)
    check(pairs == cfg.num_layers * cfg.experts_per_token
          * sum(r['tokens'] for r in windows),
          f'{pairs} routed pairs over the decode windows')
    check(0.3 < held / pairs < 0.7, f'{held} of {pairs} pairs held')
    memory = _memory_stats()
    engine.shutdown()
    del engine
    gc.collect()
    return {
        'build_s': round(build_s, 1), 'generate_s': round(generate_s, 1),
        'state_pool_bytes': settings['max_num_seqs'] * per_slot,
        'moe_held_pair_share': round(held / pairs, 4),
        'memory': memory,
    }


# ---------------------------------------------------------------- windowed
WINDOWED_MODEL = {
    'model_type': 'laguna', 'vocab_size': 512, 'hidden_size': 256,
    'intermediate_size': 512, 'num_hidden_layers': 5,
    'num_attention_heads': 12, 'num_key_value_heads': 2, 'head_dim': 128,
    'max_position_embeddings': 4096, 'attention_bias': False,
    'rms_norm_eps': 1e-6, 'num_experts': 4, 'num_routed_experts': 8,
    'first_local_expert': 0, 'num_experts_per_tok': 2,
    'moe_intermediate_size': 128, 'shared_expert_intermediate_size': 128,
    'tie_word_embeddings': False, 'gating': True, 'sliding_window': 64,
    'rope_parameters': {
        'full_attention': {
            'rope_theta': 500000, 'rope_type': 'yarn', 'factor': 8,
            'original_max_position_embeddings': 128, 'beta_slow': 1,
            'beta_fast': 8, 'attention_factor': 1.2,
            'partial_rotary_factor': 0.5,
        },
        'sliding_attention': {
            'rope_type': 'default', 'rope_theta': 10000,
            'partial_rotary_factor': 1,
        },
    },
    'layer_types': ['full_attention'] + ['sliding_attention'] * 3
    + ['full_attention'],
    'moe_apply_router_weight_on_input': False,
    'mlp_layer_types': ['dense'] + ['sparse'] * 4,
    'moe_routed_scaling_factor': 2.5,
    'num_attention_heads_per_layer': [12, 16, 16, 16, 12],
}
WINDOWED_PROMPT_TOKENS = 300  # past window + chunk (64 + 128)
WINDOWED_OUTPUT_TOKENS = 40
# Token gap to the float32 reference, bf16 program: the benchmark cell's
# limit on the largest gap (benchmarks/reference_laguna.py).
WINDOWED_GAP_LIMIT = 0.85


def phase_windowed(seed: int) -> dict:
    """A toy model with a windowed cache group through ``generate_ids``:
    prefill past the window, decode, one preemption, against the reference."""
    import jax
    import numpy as np

    from benchmarks import reference_laguna
    from distllm_tpu.generate.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distllm_tpu.models import laguna

    cfg = laguna.LagunaConfig.from_hf_config(WINDOWED_MODEL)
    params = laguna.init_on_device(jax.random.PRNGKey(seed % 2**31), cfg)

    class NoTokenizer:
        eos_id = None

    # 42 usable blocks of 16 tokens: two rows of 300 + 40 tokens need 44.
    # The engine owns the weights, as the benchmark's driver and
    # TpuGenerator have it: they are moved into the decode window's
    # layouts, but for the 12- and 16-wide gate kernels, which stay in the
    # device's default (``engine.auto_layout_formats``: left to the window,
    # the 12-wide one took a layout the prefill program failed on, "expected
    # parameter 6 of size 12288 ... got 16384"). The reference gets the
    # same tree made again from the seed.
    with _jax_cache_floor():
        engine = LLMEngine(
            cfg, params, NoTokenizer(),
            EngineConfig(block_size=16, num_blocks=43, max_num_seqs=2,
                         max_model_len=512, prefill_chunk_tokens=128,
                         enable_prefix_cache=False, attn_backend='auto'),
            own_params=True,
        )
    del params
    check(engine.telemetry['attn_backend'] == 'pallas',
          f"attn_backend resolved to {engine.telemetry['attn_backend']!r}")
    pools = engine.telemetry['kv_pools']
    check((pools['full']['layers'], pools['window']['layers'],
           pools['window']['window']) == (2, 3, 64), f'pools {pools}')
    # As if finished requests had used none of their budgets: the look-ahead
    # then admits both rows, and the pool runs short under them.
    engine._ewma['budget_use'] = 0.0
    rng = np.random.default_rng(seed)
    prompts = [
        [int(t) for t in rng.integers(4, cfg.vocab_size, WINDOWED_PROMPT_TOKENS)]
        for _ in range(2)
    ]
    before = engine.flight.total_recorded
    out = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=WINDOWED_OUTPUT_TOKENS)
    )
    records = engine.flight.snapshot()[-(engine.flight.total_recorded - before):]
    check([len(o) for o in out] == [WINDOWED_OUTPUT_TOKENS] * 2,
          f'generated {[len(o) for o in out]} tokens')
    preempts = [r for r in records if r['kind'] == 'preempt']
    check(len(preempts) >= 1, f'{len(preempts)} preemptions, one expected')
    decodes = [r for r in records if r['kind'] == 'decode']
    bound = engine._window_decode_bound
    check(all(r['kv_blocks_window'] <= 2 * bound for r in decodes),
          f'window group holds over its bound of {bound} a row')
    check(max(r['kv_blocks_full'] for r in decodes)
          > 2 * max(r['kv_blocks_window'] for r in decodes),
          'the window group holds as much as the full group')
    freed = sum(r['window_blocks_freed'] for r in records if 'window_blocks_freed' in r)
    check(freed > 0 and engine.window_blocks.num_held == 0,
          f'{freed} blocks freed, {engine.window_blocks.num_held} still held')
    engine.shutdown()
    del engine
    gc.collect()
    params = laguna.init_on_device(jax.random.PRNGKey(seed % 2**31), cfg)
    gaps = []
    for prompt, tokens in zip(prompts, out):
        ids = np.asarray([prompt + tokens[:-1]], np.int32)
        at = len(prompt) - 1 + np.arange(len(tokens))[None]
        logits = reference_laguna.laguna_logits(
            params, WINDOWED_MODEL, ids, at
        )
        gaps.append(float(reference_laguna.token_gaps(logits, [tokens]).max()))
    check(max(gaps) <= WINDOWED_GAP_LIMIT, f'token gaps {gaps}')
    return {
        'preemptions': len(preempts), 'window_blocks_freed': freed,
        'token_gap_max_std': round(max(gaps), 4),
    }


# ------------------------------------------------------------------ latent
LATENT_MODEL = {
    'model_type': 'deepseek_v3', 'vocab_size': 512, 'hidden_size': 256,
    'intermediate_size': 512, 'num_hidden_layers': 3,
    'num_attention_heads': 32, 'num_key_value_heads': 32, 'head_dim': 64,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'qk_head_dim': 192,
    'v_head_dim': 128, 'kv_lora_rank': 512, 'q_lora_rank': None,
    'max_position_embeddings': 4096, 'attention_bias': False,
    'hidden_act': 'silu', 'rms_norm_eps': 1e-6, 'first_k_dense_replace': 1,
    'moe_layer_freq': 1, 'n_routed_experts': 4, 'num_routed_experts': 8,
    'first_local_expert': 0, 'n_shared_experts': 2, 'num_experts_per_tok': 2,
    'moe_intermediate_size': 128, 'n_group': 1, 'topk_group': 1,
    'norm_topk_prob': True, 'scoring_func': 'sigmoid',
    'topk_method': 'noaux_tc', 'routed_scaling_factor': 2.448,
    'rope_theta': 1000000, 'rope_scaling': None, 'rope_interleave': True,
    'tie_word_embeddings': False,
}
LATENT_PROMPT_TOKENS = 300  # past two chunks of 128
LATENT_OUTPUT_TOKENS = 40
# Token gap to the float32 reference, bf16 program: the windowed phase's
# limit. (The benchmark cell's own, 3.2, is for a router over 128 experts,
# where rounding changes the kept set; 2 of 8 here leave it be.)
LATENT_GAP_LIMIT = 0.85


def phase_latent(seed: int) -> dict:
    """A toy model with a latent cache group through ``generate_ids``:
    prefill past two chunks, decode, one preemption, against the reference;
    then the paged kernel against its XLA twin on a latent plane."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_deepseek_v3 as reference
    from distllm_tpu.generate.engine.engine import (
        EngineConfig,
        LLMEngine,
        SamplingParams,
    )
    from distllm_tpu.models import deepseek_v3
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention_pallas,
        ragged_paged_attention_xla,
    )

    cfg = deepseek_v3.DeepseekV3Config.from_hf_config(LATENT_MODEL)
    params = deepseek_v3.init_on_device(jax.random.PRNGKey(seed % 2**31), cfg)

    class NoTokenizer:
        eos_id = None

    # 42 usable blocks of 16 tokens: two rows of 300 + 40 tokens need 44.
    with _jax_cache_floor():
        engine = LLMEngine(
            cfg, params, NoTokenizer(),
            EngineConfig(block_size=16, num_blocks=43, max_num_seqs=2,
                         max_model_len=512, prefill_chunk_tokens=128,
                         enable_prefix_cache=False, attn_backend='auto'),
            own_params=True,
        )
    del params
    check(engine.telemetry['attn_backend'] == 'pallas',
          f"attn_backend resolved to {engine.telemetry['attn_backend']!r}")
    pool = engine.telemetry['kv_pools']['latent']
    check((pool['layers'], pool['block_shape']) == (3, [16, 640])
          and engine.kv.v_pool == (), f'pool {pool}')
    check(pool['bytes'] == 43 * 16 * 640 * 2 * 3, f"pool of {pool['bytes']} bytes")
    engine._ewma['budget_use'] = 0.0  # admit both rows: the pool runs short
    rng = np.random.default_rng(seed)
    prompts = [
        [int(t) for t in rng.integers(4, cfg.vocab_size, LATENT_PROMPT_TOKENS)]
        for _ in range(2)
    ]
    before = engine.flight.total_recorded
    out = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=LATENT_OUTPUT_TOKENS)
    )
    records = engine.flight.snapshot()[-(engine.flight.total_recorded - before):]
    check([len(o) for o in out] == [LATENT_OUTPUT_TOKENS] * 2,
          f'generated {[len(o) for o in out]} tokens')
    preempts = [r for r in records if r['kind'] == 'preempt']
    check(len(preempts) >= 1, f'{len(preempts)} preemptions, one expected')
    chunks = [r for r in records if r['kind'] == 'prefill']
    check(len(chunks) >= 3, f'{len(chunks)} prefill dispatches, 3 or more expected')
    engine.shutdown()
    del engine
    gc.collect()
    params = deepseek_v3.init_on_device(jax.random.PRNGKey(seed % 2**31), cfg)
    gaps = []
    for prompt, tokens in zip(prompts, out):
        ids = np.asarray([prompt + tokens[:-1]], np.int32)
        at = len(prompt) - 1 + np.arange(len(tokens))[None]
        logits = reference.deepseek_logits(params, LATENT_MODEL, ids, at)
        gaps.append(float(reference.token_gaps(logits, [tokens]).max()))
    check(max(gaps) <= LATENT_GAP_LIMIT, f'token gaps {gaps}')
    del params
    # The kernel against its twin: decode rows and a chunk's rows.
    plane = jnp.asarray(rng.standard_normal((64, 16, 640)), jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(63)[:60].reshape(3, 20), jnp.int32)
    ctx = jnp.asarray([300, 17, 129], jnp.int32)
    worst = 0.0
    for span in (1, 16):
        q = jnp.asarray(rng.standard_normal((3, span, 32, 640)) * 0.3, jnp.bfloat16)
        pos = (ctx - span)[:, None] + jnp.arange(span)[None]
        kw = dict(scale=192 ** -0.5, value_lanes=512)
        got = ragged_paged_attention_pallas(q, plane, None, tables, ctx, pos, **kw)
        want = ragged_paged_attention_xla(q, plane, None, tables, ctx, pos, **kw)
        err, ok = _max_err(got, want)
        worst = max(worst, err)
        check(ok, f'latent kernel vs twin, span {span}: max abs err {err}')
    return {
        'preemptions': len(preempts), 'prefill_dispatches': len(chunks),
        'token_gap_max_std': round(max(gaps), 4),
        'kernel_vs_twin': round(worst, 5),
    }


def generator_settings(model_dir: Path) -> dict:
    """``generator_config`` of the documented start
    (examples/chat/chat_server.rag.yaml), pointed at the seed-made
    checkpoint, with the prefix cache on."""
    import yaml

    example = yaml.safe_load(
        (REPO / 'examples' / 'chat' / 'chat_server.rag.yaml').read_text()
    )
    settings = dict(example['generator_config'])
    settings['pretrained_model_name_or_path'] = str(model_dir)
    settings['max_tokens'] = MAX_TOKENS
    settings['enable_prefix_cache'] = True
    return settings


def _memory_stats() -> dict:
    """The device's own byte counts, where the backend reports them."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {
        key: stats.get(key)
        for key in ('bytes_in_use', 'peak_bytes_in_use', 'bytes_limit')
    }


# What the engine counted for ONE request: ``generate_ids`` clears
# ``engine._stats`` at the start of every call and the requests go one at
# a time (``engine.telemetry`` keeps a finished request's keys).
REQUEST_COUNTERS = (
    'prefix_lookup_tokens',  # prompt tokens admitted (prefix cache on)
    'prefix_hit_tokens', 'prefill_dispatches', 'prefill_chunks',
    'decode_windows',
)


async def _drive_server(app, engine, prompts: dict[str, str]) -> dict:
    """Start the app on a free local port, send the requests one after the
    other (the shared-prefix pair must not race), stop the server."""
    import aiohttp
    from aiohttp import web

    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, '127.0.0.1', 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    base = f'http://127.0.0.1:{port}'
    out: dict = {'requests': {}}
    timeout = aiohttp.ClientTimeout(total=900)
    try:
        async with aiohttp.ClientSession(timeout=timeout) as session:
            async with session.get(f'{base}/health') as resp:
                out['health'] = resp.status
            for name, prompt in prompts.items():
                start = time.perf_counter()
                async with session.post(
                    f'{base}/v1/chat/completions',
                    json={'messages': [{'role': 'user', 'content': prompt}]},
                ) as resp:
                    body = await resp.json()
                    content = (
                        body['choices'][0]['message']['content']
                        if resp.status == 200 else ''
                    )
                    note(f'serve: request {name} answered {resp.status}')
                    out['requests'][name] = {
                        'status': resp.status,
                        'prompt_words': len(prompt.split()),
                        'content_words': len(content.split()),
                        'seconds': round(time.perf_counter() - start, 2),
                        **{
                            key: int(engine._stats.get(key, 0))
                            for key in REQUEST_COUNTERS
                        },
                    }
            async with session.get(f'{base}/metrics') as resp:
                out['metrics_status'] = resp.status
                out['metrics_text'] = await resp.text()
    finally:
        await runner.cleanup()
    return out


def _metric_total(text: str, name: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith('#'):
            total += float(line.rsplit(' ', 1)[1])
    return total


def phase_serve(seed: int) -> dict:
    import numpy as np

    from distllm_tpu import chat_server
    from distllm_tpu.chat import ChatAppConfig
    from distllm_tpu.registry import registry

    model_dir = WORK / 'mistral'
    start = time.perf_counter()
    written_gb = write_mistral_checkpoint(model_dir, MISTRAL_7B, seed)
    write_s = time.perf_counter() - start
    note(f'serve: wrote {written_gb:.2f} GB of checkpoint in {write_s:.0f}s')
    settings = generator_settings(model_dir)
    compiled_before = compile_counts()

    start = time.perf_counter()
    app = chat_server.build_app(ChatAppConfig(generator_config=settings))
    load_s = time.perf_counter() - start
    note(f'serve: server and engine built in {load_s:.0f}s')
    generator = registry().active['generator']
    engine = generator.engine
    memory_after_load = _memory_stats()

    rng = np.random.default_rng(seed + 1)
    vocab = MISTRAL_7B['vocab_size']
    max_len = engine.config.max_model_len
    block = engine.config.block_size
    chunk = engine.config.prefill_chunk_tokens
    prefix_words = max_len // 8
    prefix = make_words(rng, prefix_words, vocab)
    # The chat template adds a few tokens to every prompt (one word of the
    # seed vocabulary is one token).
    prompts = {
        'shared_prefix_a': f'{prefix} {make_words(rng, 24, vocab)}',
        'shared_prefix_b': f'{prefix} {make_words(rng, 40, vocab)}',
        'short': make_words(rng, 12, vocab),
        'near_max_model_len': make_words(rng, max_len - MAX_TOKENS - 64, vocab),
    }
    prompts['shared_prefix_a_again'] = prompts['shared_prefix_a']
    served = asyncio.run(_drive_server(app, engine, prompts))
    metrics_text = served.pop('metrics_text')
    compiled_after = compile_counts()

    check(served['health'] == 200, f"/health answered {served['health']}")
    requests = served['requests']
    for name, result in requests.items():
        check(result['status'] == 200, f'{name}: HTTP {result["status"]}')
        check(result['content_words'] > 0, f'{name}: empty content')
        # Nothing between the HTTP body and the scheduler cut the prompt.
        check(
            result['prefix_lookup_tokens'] >= result['prompt_words'],
            f'{name}: {result["prompt_words"]} words sent, the engine '
            f'admitted {result["prefix_lookup_tokens"]} tokens',
        )
    long_request = requests['near_max_model_len']
    check(
        long_request['prefix_lookup_tokens'] + MAX_TOKENS <= max_len,
        f'the long prompt leaves no room to generate: {long_request}',
    )
    check(
        chunk and long_request['prefill_chunks']
        >= long_request['prompt_words'] // chunk,
        f'the long prompt was not prefilled in chunks: {long_request}',
    )
    # Two DIFFERENT prompts sharing a prefix: the second reuses the
    # prefix's whole blocks and prefills its own tail.
    second = requests['shared_prefix_b']
    check(
        prefix_words - block <= second['prefix_hit_tokens']
        < second['prefix_lookup_tokens'] - 40,
        f'shared prefix of {prefix_words} words: {second}',
    )
    generated = _metric_total(metrics_text, 'distllm_engine_generated_tokens')
    check(served['metrics_status'] == 200 and generated > 0,
          '/metrics shows no generated tokens')

    telemetry = dict(engine.telemetry)
    check(
        telemetry.get('attn_backend') == 'pallas',
        f"attn_backend resolved to {telemetry.get('attn_backend')!r}",
    )
    fallbacks = sorted(k for k in telemetry if k.endswith('_fallback'))
    check(not fallbacks, f'fallback telemetry: '
          f'{ {k: telemetry[k] for k in fallbacks} }')
    scheduler = type(engine.sched._inner).__name__
    check(scheduler == 'NativeScheduler', f'scheduler is {scheduler}')
    hit_blocks = int(engine.prefix_cache.stats['hit_blocks'])
    check(hit_blocks > 0, 'the prefix cache counted no hit')

    fields = {
        'layers': MISTRAL_7B['num_hidden_layers'],
        'layer_cut': False,
        'checkpoint_gb': round(written_gb, 2),
        'checkpoint_write_seconds': round(write_s, 1),
        'load_seconds': round(load_s, 1),
        'settings': {
            k: v for k, v in settings.items()
            if k != 'pretrained_model_name_or_path'
        },
        'max_model_len': max_len,
        'tokenizer_max_length': engine.tokenizer.model_max_length,
        'num_blocks': engine.config.num_blocks,
        'kv_pool_gib': round(engine.kv.hbm_bytes / 2**30, 3),
        'memory_after_load': memory_after_load,
        'memory_after_requests': _memory_stats(),
        'health': served['health'],
        'requests': requests,
        'first_request_seconds': requests['shared_prefix_a']['seconds'],
        'repeated_request_seconds': requests['shared_prefix_a_again'][
            'seconds'],
        'generated_tokens_metric': generated,
        'attn_backend': telemetry.get('attn_backend'),
        'telemetry': telemetry,
        'scheduler': scheduler,
        'prefix_cache_hit_blocks': hit_blocks,
        'compiled_before': compiled_before,
        'compiled_after': compiled_after,
    }
    registry().clear()  # shuts the engine down and frees its arrays
    return fields


# ------------------------------------------------------------- four chips


def _greedy_engine_tokens(generator, prompt_ids: list[list[int]], steps: int):
    from distllm_tpu.generate.engine import SamplingParams

    return generator.engine.generate_ids(
        prompt_ids, SamplingParams(temperature=0.0, max_tokens=steps)
    )


def _prefill_logits(engine, prompt: list[int]):
    """Last-position prefill logits through the engine's own jitted
    prefill (the bucket the serving path would use)."""
    import numpy as np

    from distllm_tpu.models.tokenizer import pick_bucket

    bucket = pick_bucket(len(prompt), engine.prefill_buckets)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, : len(prompt)] = prompt
    mask = (np.arange(bucket)[None, :] < len(prompt)).astype(np.int32)
    logits, _, _ = engine._prefill(
        engine.params, engine._put(ids), engine._put(mask),
        engine._put(np.asarray([len(prompt) - 1], np.int32)),
    )
    return np.asarray(logits, np.float32)[0]


# TP-4 against one chip. Both engines score the SAME token prefixes (the
# one-chip engine's greedy tokens, teacher-forced), so the two logit
# vectors differ by bf16 reduction order only. The statistic was chosen
# after the first four-chip call failed a worse one (max-abs against a
# fraction of the std), and the limits were calibrated afterwards on ONE
# chip (PERF.md, PR 21): the same prefixes through the dense prefill and
# through the paged prefill, Pallas and XLA, which is the same math in
# another order, differ by a relative RMS of 0.050-0.059. The limit is
# 1.5 times that floor. What it can see is a fault that touches every
# layer (a wrong collective; a fault injected into layer 0 gave 1.2-1.4).
# What it cannot see is a fault confined to one late layer: a quarter of
# one layer's value heads dropped or swapped moved the logits by 0.02-0.11,
# inside the floor. Weight placement is therefore checked exactly, by
# `_weight_checksums`, not by this number.
TP_REL_RMS = 0.09
# No single logit may be off by more than noise allows: the largest of
# 32768 Gaussian differences is about 4.2 RMS, the one-chip floor showed
# 3.9-5.2 and the first four-chip call 4.4.
TP_MAX_OVER_RMS = 7.0
# Greedy tokens may part where that noise reorders the top two. The
# difference of two noisy logits, on the decode path against the prefill
# path as well as across chips, has a standard deviation near 2 RMS, so 6
# RMS is three of them. Random weights give flat logits (the one-chip
# top-two gap was under one RMS at 7 of the 16 calibration steps), so
# this only rules out a parting at a step with a clear winner.
TP_TIE_RMS_MULTIPLE = 6.0


def _weight_checksums(params) -> dict[str, int]:
    """One integer per weight leaf that is exact whatever the sharding, the
    device layout or the reduction order: the sum over elements of
    ``bits(x_i) * (2 i + 1)`` in wrapping uint32 arithmetic, ``i`` the
    element's row-major index in its leading-axis slice, the slices
    combined the same way. Equal checksums on two engines mean the same
    numbers in the same logical places."""
    import jax
    import jax.numpy as jnp

    unsigned = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}

    def flat(x):
        bits = jax.lax.bitcast_convert_type(
            x, unsigned[x.dtype.itemsize]
        ).astype(jnp.uint32)
        odd = 2 * jnp.arange(x.size, dtype=jnp.uint32).reshape(x.shape) + 1
        return jnp.sum(bits * odd, dtype=jnp.uint32)

    @jax.jit
    def checksum(x):
        if x.ndim < 2:
            return flat(x)
        # One leading-axis slice at a time: a 3.5 GiB leaf never needs a
        # uint32 copy of itself beside a full HBM.
        return flat(jax.lax.map(flat, x))

    return {
        jax.tree_util.keystr(path): int(checksum(leaf))
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
    }


def _step_logits(engine, prompt: list[int], tokens: list[int]) -> list:
    """``logits[s]`` follows ``prompt + tokens[:s]`` (teacher-forced)."""
    return [
        _prefill_logits(engine, prompt + tokens[:s])
        for s in range(len(tokens))
    ]


def phase_tp4(seed: int) -> dict:
    import jax
    import numpy as np

    from distllm_tpu.generate import get_generator

    model_dir = WORK / 'mistral'
    written_gb = write_mistral_checkpoint(model_dir, MISTRAL_7B, seed)
    note(f'tp4: wrote {written_gb:.2f} GB of checkpoint')
    rng = np.random.default_rng(seed + 2)
    vocab = MISTRAL_7B['vocab_size']
    prompts = [
        [int(t) for t in rng.integers(2, vocab, size=n)] for n in (48, 96)
    ]

    def build(tp: int):
        settings = generator_settings(model_dir)
        settings['tensor_parallel_size'] = tp
        return get_generator(settings, register=False)

    # One chip first; shut it down and free its arrays before the mesh
    # engine loads, or device 0 holds both and runs out.
    start = time.perf_counter()
    single = build(1)
    single_load_s = time.perf_counter() - start
    note(f'tp4: one-chip engine built in {single_load_s:.0f}s')
    single_tokens = [
        [int(t) for t in row]
        for row in _greedy_engine_tokens(single, prompts, TP_STEPS)
    ]
    single_logits = [
        _step_logits(single.engine, p, t)
        for p, t in zip(prompts, single_tokens)
    ]
    single_backend = dict(single.engine.telemetry)
    single_sums = _weight_checksums(single.engine.params)
    single.shutdown()
    del single
    gc.collect()

    start = time.perf_counter()
    sharded = build(4)
    sharded_load_s = time.perf_counter() - start
    note(f'tp4: TP-4 engine built in {sharded_load_s:.0f}s')
    engine = sharded.engine
    # Per-device bytes of the sharded weights, read from the arrays' own
    # addressable shards: "everything on the first device" cannot pass.
    per_device: dict[int, int] = {}
    total = 0
    for leaf in jax.tree.leaves(engine.params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )
    shares = {d: b / total for d, b in sorted(per_device.items())}
    check(len(shares) == 4, f'weights live on {len(shares)} devices')
    # Replicated leaves (the embedding table, norm scales) put each share
    # a little above a quarter.
    check(max(shares.values()) < 0.35, f'per-device weight share {shares}')
    # ... and the shards together are the one-chip engine's weights,
    # number for number: a leaf sharded into the wrong places fails here,
    # exactly, however small its share of the logits.
    sharded_sums = _weight_checksums(engine.params)
    differing = sorted(
        k for k in single_sums if single_sums[k] != sharded_sums.get(k)
    )
    check(
        not differing and len(sharded_sums) == len(single_sums),
        f'sharded weights differ from the one-chip weights in {differing}',
    )

    sharded_tokens = [
        [int(t) for t in row]
        for row in _greedy_engine_tokens(sharded, prompts, TP_STEPS)
    ]
    sharded_logits = [
        _step_logits(engine, p, t) for p, t in zip(prompts, single_tokens)
    ]
    telemetry = dict(engine.telemetry)
    sharded.shutdown()

    agreement = []
    for one, four, logits1, logits4 in zip(
        single_tokens, sharded_tokens, single_logits, sharded_logits
    ):
        same = next(
            (i for i, (a, b) in enumerate(zip(one, four)) if a != b), len(one)
        )
        rms = [
            float(np.sqrt(np.mean((l1 - l4) ** 2)))
            for l1, l4 in zip(logits1, logits4)
        ]
        rel_rms = [r / float(l1.std()) for r, l1 in zip(rms, logits1)]
        max_over_rms = [
            float(np.abs(l1 - l4).max()) / r
            for r, l1, l4 in zip(rms, logits1, logits4)
        ]
        record = {
            'tokens_one_chip': one,
            'tokens_tp4': four,
            'agree_first_steps': same,
            'logits_rel_rms_diff_per_step': [round(r, 4) for r in rel_rms],
            'logits_max_over_rms_per_step': [
                round(m, 2) for m in max_over_rms
            ],
        }
        check(
            max(rel_rms) < TP_REL_RMS,
            f'logits differ by more than reduction order: {record}',
        )
        check(
            max(max_over_rms) < TP_MAX_OVER_RMS,
            f'single logits differ by more than the noise: {record}',
        )
        check(same >= 1, f'greedy tokens never agree: {record}')
        if same < len(one):
            l1 = logits1[same]
            gap = float(l1[one[same]] - l1[four[same]])
            record.update(
                parted_at_step=same,
                one_chip_gap_between_the_two_tokens=gap,
                logits_rms_diff_at_that_step=rms[same],
            )
            check(
                abs(gap) <= TP_TIE_RMS_MULTIPLE * rms[same],
                f'tokens part without a near-tie: {record}',
            )
        agreement.append(record)
    return {
        'layers': MISTRAL_7B['num_hidden_layers'],
        'checkpoint_gb': round(written_gb, 2),
        'one_chip_load_seconds': round(single_load_s, 1),
        'tp4_load_seconds': round(sharded_load_s, 1),
        'weight_bytes_total': total,
        'weight_share_per_device': shares,
        'weight_leaves_checksum_equal': len(sharded_sums),
        'one_chip_backends': single_backend,
        'tp4_backends': telemetry,
        'rel_rms_limit': TP_REL_RMS,
        'max_over_rms_limit': TP_MAX_OVER_RMS,
        'tie_rms_multiple': TP_TIE_RMS_MULTIPLE,
        'greedy': agreement,
    }


def phase_index4(seed: int) -> dict:
    import numpy as np
    from datasets import Dataset

    from distllm_tpu.rag.search import TpuIndexV2Config

    work = WORK / 'index'
    dim = PUBMEDBERT['hidden_size']
    rng = np.random.default_rng(seed + 3)
    shard_rows = 1 << 16
    first = None
    for part, lo in enumerate(range(0, INDEX_ROWS, shard_rows)):
        rows = rng.standard_normal(
            (min(shard_rows, INDEX_ROWS - lo), dim), dtype=np.float32
        )
        rows /= np.linalg.norm(rows, axis=1, keepdims=True)
        if first is None:
            first = rows[:4096].copy()
        Dataset.from_dict({'embeddings': rows}).save_to_disk(
            str(work / 'dataset' / f'{part:05d}')
        )
    # Queries are noisy copies of corpus rows, so the corpus has real
    # nearest neighbours (pure-random vectors have none).
    src = first[rng.integers(0, len(first), size=INDEX_QUERIES)]
    queries = src + (0.5 / np.sqrt(dim)) * rng.standard_normal(
        src.shape, dtype=np.float32
    )
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)

    out = {'rows': INDEX_ROWS, 'dim': dim, 'queries': len(queries)}
    for precision in ('float32', 'int8'):
        ids = {}
        for name, mesh in (('unsharded', None), ('sharded', {'data': -1})):
            start = time.perf_counter()
            index = TpuIndexV2Config(
                dataset_dir=work / 'dataset',
                index_dir=work / 'index_files',
                precision=precision,
                mesh=mesh,
            ).get_index()
            results = index.search(queries, top_k=10, score_threshold=-1.0)
            ids[name] = results.total_indices
            out[f'{precision}_{name}_seconds'] = round(
                time.perf_counter() - start, 1
            )
            if mesh is not None:
                arrays = (
                    index._int8 if precision == 'int8' else (index._corpus,)
                )
                devices = {
                    s.device.id for a in arrays for s in a.addressable_shards
                }
                out[f'{precision}_sharded_devices'] = len(devices)
                check(len(devices) == 4, f'{precision}: index on {devices}')
            del index
            gc.collect()
        check(
            all(len(row) == 10 for row in ids['sharded']),
            f'{precision}: sharded search returned short rows',
        )
        out[f'{precision}_top10_equal'] = ids['sharded'] == ids['unsharded']
        check(
            out[f'{precision}_top10_equal'],
            f'{precision}: sharded top-10 ids differ from unsharded',
        )
    return out


# -------------------------------------------------------------------- main


def run(chips: int, seed: int) -> int:
    from distllm_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # Cache every compile, however short: a second start then compiles
    # nothing, and "no new entries" is a clean check (jax's default skips
    # compiles under a second, which straddle the threshold run to run).
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)

    devices = jax.devices()
    if devices[0].platform != 'tpu':
        print(
            f'no TPU: jax found platform {devices[0].platform!r} '
            f'({len(devices)} device(s)); chip_smoke.py runs on the chip only',
            flush=True,
        )
        return 2
    if len(devices) != chips:
        print(
            f'no TPU set-up for this run: {len(devices)} chip(s) attached, '
            f'--chips {chips} asked for', flush=True,
        )
        return 2

    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    try:
        ok = run_phase('device', phase_device, cache_dir)
        if chips == 4:
            phases = (('tp4', phase_tp4), ('index4', phase_index4))
        else:
            phases = (
                ('kernels', phase_kernels), ('embed', phase_embed),
                ('hybrid', phase_hybrid), ('windowed', phase_windowed),
                ('latent', phase_latent),
                ('serve', phase_serve),
            )
        for name, fn in phases:
            ok = run_phase(name, fn, seed) and ok
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    if not ok:
        return 1
    emit({
        'ok': True,
        'device': {
            'platform': devices[0].platform,
            'kind': devices[0].device_kind,
            'count': len(devices),
        },
    })
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument(
        '--chips', type=int, choices=(1, 4), default=1,
        help='4 = only the across-chips phases (tp4, index4)',
    )
    args = parser.parse_args(argv)
    return run(args.chips, args.seed)


if __name__ == '__main__':
    raise SystemExit(main())
