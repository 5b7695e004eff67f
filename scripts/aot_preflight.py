"""Compile-only preflight of the serving path against a described v5e.

The Mosaic lowering and the HBM budget are invisible to CPU interpret
tests. The installed libtpu compiles for a chip that is described and not
attached (``jax.experimental.topologies``), so every serving executable —
bf16 batch-32 and int8 batch-128 fused decode windows, both attention
backends, with the engine's AUTO-layout compile, and the tensor-parallel
window on the four-chip ``v5e:2x2`` mesh with the shardings
``TpuGenerator`` applies — is checked for lowering errors and memory fit
before a chip-second is spent. A compile that passes is not a chip run.
See tests/test_aot_tpu.py for the kernel-only tier-1 version.

Run: ``JAX_PLATFORMS=cpu python scripts/aot_preflight.py [single] [multichip] [embed]``
(no argument = all three sets).
"""

import argparse
import os

_ALL_SETS = ['single', 'multichip', 'embed']
_parser = argparse.ArgumentParser(
    description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
)
_parser.add_argument('sets', nargs='*', help=f'of {_ALL_SETS}; default all')
SETS = _parser.parse_args().sets or _ALL_SETS  # before libtpu is loaded
if set(SETS) - set(_ALL_SETS):
    _parser.error(f'unknown set in {SETS}: choose from {_ALL_SETS}')

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
os.environ.setdefault('TPU_LOG_DIR', 'disabled')
import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding
import pathlib, sys
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import time

# A compile for a described chip is written to the persistent cache but
# cannot be read back by a chip, so this script keeps the cache off.
jax.config.update('jax_enable_compilation_cache', False)

topo = topologies.get_topology_desc(platform='tpu', topology_name='v5e:2x2')
s = SingleDeviceSharding(topo.devices[0])

def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=s)

from distllm_tpu.models import mistral
from distllm_tpu.ops.quantization import quantize_pytree_abstract

mcfg = mistral.MistralConfig(dtype='bfloat16')
mshapes = jax.eval_shape(lambda: mistral.init_on_device(jax.random.PRNGKey(0), mcfg))
bs = 16

def window_args(params_tree, B, nb, R):
    kshape = (mcfg.num_layers, nb, bs, mcfg.num_kv_heads * mcfg.head_size)
    return (
        params_tree, sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B,), jnp.int32), sds(kshape, jnp.bfloat16),
        sds(kshape, jnp.bfloat16), sds((B, R), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.float32),
        sds((B,), jnp.float32), sds((B,), jnp.float32),
        sds((B,), jnp.int32), sds((B,), jnp.uint32),
    )

failures: list[str] = []


def compile_window(params_tree, B, nb, R, backend, label):
    t = time.perf_counter()
    try:
        fn = lambda p, i, po, c, k, v, bt, sl, tmp, tp, mp, tk, sd: \
            mistral.decode_loop(
                p, mcfg, i, po, k, v, bt, c, sl, tmp, tp, mp, tk, sd,
                num_steps=16, attn_backend=backend, max_table_positions=512,
                sampling_top_window=64)
        jitted = jax.jit(fn, donate_argnums=(4, 5),
                         in_shardings=(Format(Layout.AUTO),) + (Format(),) * 12)
        compiled = jitted.lower(*window_args(params_tree, B, nb, R)).compile()
        mem = compiled.memory_analysis()
        print(f'{label}: AOT OK ({time.perf_counter()-t:.0f}s) '
              f'temp={mem.temp_size_in_bytes/1e9:.3f}GB', flush=True)
    except Exception as exc:
        print(f'{label}: FAILED {repr(exc)[:400]}', flush=True)
        failures.append(label)

if 'single' in SETS:
    bf16_params = jax.tree.map(lambda x: sds(x.shape, x.dtype), mshapes)
    compile_window(bf16_params, 32, 712, 32, 'pallas', 'bf16 B=32 pallas AUTO-layout')
    compile_window(bf16_params, 32, 712, 32, 'xla', 'bf16 B=32 xla AUTO-layout')

    qparams = quantize_pytree_abstract(mshapes, make_leaf=sds)
    compile_window(qparams, 128, 2840, 32, 'pallas', 'int8 B=128 pallas AUTO-layout')
    compile_window(qparams, 128, 2840, 32, 'xla', 'int8 B=128 xla AUTO-layout')
    print('SINGLE-CHIP CASES DONE', flush=True)


# ---- multi-chip lowering: SP ring attention + TP decode on real v5e devices
# (the CPU virtual mesh exercises semantics; this validates the TPU/ICI
# lowering of the same programs).
def compile_multichip() -> None:
    from distllm_tpu.ops.ring_attention import ring_attention

    t = time.perf_counter()
    try:
        sp_mesh = Mesh(np.asarray(topo.devices[:2]).reshape(1, 2), ('data', 'seq'))
        rs = NamedSharding(sp_mesh, P(None, 'seq', None, None))
        ms = NamedSharding(sp_mesh, P(None, 'seq'))
        B, S, N, H = 2, 256, 8, 128
        jax.jit(
            lambda q, k, v, m: ring_attention(
                q, k, v, sp_mesh, kv_mask=m, causal=True
            )
        ).lower(
            jax.ShapeDtypeStruct((B, S, N, H), jnp.bfloat16, sharding=rs),
            jax.ShapeDtypeStruct((B, S, N, H), jnp.bfloat16, sharding=rs),
            jax.ShapeDtypeStruct((B, S, N, H), jnp.bfloat16, sharding=rs),
            jax.ShapeDtypeStruct((B, S), jnp.bool_, sharding=ms),
        ).compile()
        print(f'SP ring attention 2-dev v5e: AOT OK '
              f'({time.perf_counter()-t:.0f}s)', flush=True)
    except Exception as exc:
        print(f'SP ring attention: FAILED {repr(exc)[:400]}', flush=True)
        failures.append('ring')

    t = time.perf_counter()
    try:
        # The mesh and shardings TpuGenerator builds for
        # tensor_parallel_size=4 (tpu_backend.py): make_mesh over the
        # first four devices, param_specs on the model axis, KV pages
        # sharded over the kv-head dim, step inputs replicated.
        from distllm_tpu.parallel.mesh import MeshSpec, make_mesh

        tp_mesh = make_mesh(
            MeshSpec(data=1, model=4), devices=list(topo.devices)[:4]
        )
        repl = NamedSharding(tp_mesh, P())
        kvs = NamedSharding(tp_mesh, P(None, None, None, 'model'))
        tp_params = jax.tree.map(
            lambda spec, leaf: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=NamedSharding(tp_mesh, spec)
            ),
            mistral.param_specs(mcfg, mshapes), mshapes,
            is_leaf=lambda x: isinstance(x, P),
        )
        B, nb, R = 32, 640, 256
        ksh = (mcfg.num_layers, nb, bs, mcfg.num_kv_heads * mcfg.head_size)
        def r(shape, dtype):
            return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=repl)
        compiled = jax.jit(
            lambda p, i, po, c, k, v, bt, sl, tmp, tp_, mp, tk, sd:
                mistral.decode_loop(
                    p, mcfg, i, po, k, v, bt, c, sl, tmp, tp_, mp, tk, sd,
                    num_steps=8, attn_backend='xla', max_table_positions=4096,
                    sampling_top_window=0),
            donate_argnums=(4, 5),
        ).lower(
            tp_params, r((B,), jnp.int32), r((B,), jnp.int32),
            r((B,), jnp.int32),
            jax.ShapeDtypeStruct(ksh, jnp.bfloat16, sharding=kvs),
            jax.ShapeDtypeStruct(ksh, jnp.bfloat16, sharding=kvs),
            r((B, R), jnp.int32), r((B,), jnp.int32), r((B,), jnp.float32),
            r((B,), jnp.float32), r((B,), jnp.float32), r((B,), jnp.int32),
            r((B,), jnp.uint32),
        ).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        collectives = {
            op: text.count(f' {op}(') + text.count(f' {op}-start(')
            for op in ('all-reduce', 'all-gather', 'reduce-scatter',
                       'all-to-all', 'collective-permute')
        }
        print(f'TP=4 decode window v5e:2x2: AOT OK '
              f'({time.perf_counter()-t:.0f}s) per-device '
              f'args={mem.argument_size_in_bytes/1e9:.2f}GB '
              f'temp={mem.temp_size_in_bytes/1e9:.3f}GB '
              f'collectives={collectives} '
              f'tpu_custom_call={"tpu_custom_call" in text}', flush=True)
        if not collectives['all-reduce']:
            raise RuntimeError('no all-reduce in a tensor-parallel window')
    except Exception as exc:
        print(f'TP=4 decode window: FAILED {repr(exc)[:400]}', flush=True)
        failures.append('tp')


if 'multichip' in SETS:
    compile_multichip()
    print('MULTICHIP DONE', flush=True)


# ---- embed-stage executables: the bench's other warmup set. Mirrors
# JaxEncoder.pooled_forward's fused graph (encode -> mean pool -> fp32).
def compile_embed_set() -> None:
    from distllm_tpu.embed import get_pooler
    from distllm_tpu.models import bert
    from distllm_tpu.ops.quantization import quantize_pytree_abstract

    cfg = bert.BertConfig(dtype='bfloat16')
    host = bert.init(jax.random.PRNGKey(0), cfg)
    f32_params = jax.tree.map(lambda x: sds(np.shape(x), jnp.float32), host)
    del host
    int8_params = quantize_pytree_abstract(f32_params, make_leaf=sds)
    pooler = get_pooler({'name': 'mean'})

    def fused(p, ids, mask):
        pooled = pooler.pool(bert.apply(p, cfg, ids, mask), mask)
        return pooled.astype(jnp.float32)

    for label, params in (('f32', f32_params), ('int8', int8_params)):
        for S in (160, 192, 224, 256, 288, 320, 352):
            t = time.perf_counter()
            try:
                jax.jit(fused).lower(
                    params, sds((512, S), jnp.int32), sds((512, S), jnp.int32)
                ).compile()
                print(f'embed fused {label} S={S}: AOT OK '
                      f'({time.perf_counter()-t:.0f}s)', flush=True)
            except Exception as exc:
                print(f'embed fused {label} S={S}: FAILED '
                      f'{repr(exc)[:300]}', flush=True)
                failures.append(f'embed-{label}-{S}')


if 'embed' in SETS:
    compile_embed_set()
print('PREFLIGHT DONE' + (f' ({len(failures)} FAILED)' if failures else ''),
      flush=True)
sys.exit(1 if failures else 0)
