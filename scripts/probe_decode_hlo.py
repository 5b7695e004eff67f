"""Localize the decode-window gap WITHOUT hardware: AOT cost analysis.

A 2026-07-31 record on older code (in git history; not re-measured, a
hypothesis) had the bf16 batch-32 fused 16-step window at ~845 ms against
the ~283 ms weight-streaming floor. The compiled executable itself can
testify without a chip: compile the exact serving window against the v5e
topology (libtpu, no chip) and read

- ``cost_analysis()`` bytes accessed -> a bandwidth-bound time prediction
  (bytes / 819 GB/s). If this lands near the floor, the compiled graph is
  fine and the gap is runtime-side (dispatch stalls, host latency). If it
  lands near those 845 ms, the extra HBM traffic is IN the graph —
  and the HLO says which ops carry it.
- HLO op census: copies / transposes / all-to-alls and the largest
  fusions, to name the traffic carriers.

Prints JSON lines; a pure local compile, no chip needed.
"""

from __future__ import annotations

import json
import os

os.environ.pop('JAX_PLATFORMS', None)
import collections  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.experimental.layout import Format, Layout  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from distllm_tpu.models import mistral  # noqa: E402

HBM_BW = 819e9  # v5e
PEAK_BF16 = 197e12


def main() -> None:
    topo = topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2x1'
    )
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1), ('x',))
    shard = NamedSharding(mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=shard)

    mcfg = mistral.MistralConfig(dtype='bfloat16')
    mshapes = jax.eval_shape(
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), mcfg)
    )
    mshapes = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), mshapes
    )
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(mshapes)
    )
    bs, B, nb, R, steps = 16, 32, 712, 32, 16
    kshape = (mcfg.num_layers, nb, bs, mcfg.num_kv_heads, mcfg.head_size)
    args = (
        mshapes, sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B,), jnp.int32), sds(kshape, jnp.bfloat16),
        sds(kshape, jnp.bfloat16), sds((B, R), jnp.int32),
        sds((B,), jnp.int32), sds((B,), jnp.float32),
        sds((B,), jnp.float32), sds((B,), jnp.float32),
        sds((B,), jnp.int32), sds((B,), jnp.uint32),
    )
    floor_s = steps * 2 * n_params / HBM_BW

    for backend, unroll in (
        ('pallas', False), ('pallas', True), ('xla', False), ('xla', True)
    ):
        def fn(p, i, po, c, k, v, bt, sl, tmp, tp, mp, tk, sd, be=backend,
               un=unroll):
            return mistral.decode_loop(
                p, mcfg, i, po, k, v, bt, c, sl, tmp, tp, mp, tk, sd,
                num_steps=steps, attn_backend=be, max_table_positions=512,
                sampling_top_window=64, layer_unroll=un,
            )

        jitted = jax.jit(
            fn, donate_argnums=(4, 5),
            in_shardings=(Format(Layout.AUTO),) + (Format(),) * 12,
        )
        compiled = jitted.lower(*args).compile()
        cost = compiled.cost_analysis() or {}
        if isinstance(cost, list):  # older jax returns [dict]
            cost = cost[0] if cost else {}
        flops = cost.get('flops')
        bytes_accessed = cost.get('bytes accessed')
        out = {
            'backend': backend,
            'layer_unroll': unroll,
            'window_steps': steps,
            'batch': B,
            'floor_ms': round(floor_s * 1e3, 1),
            'flops': flops,
            'bytes_accessed': bytes_accessed,
        }
        if bytes_accessed:
            out['bw_bound_ms'] = round(bytes_accessed / HBM_BW * 1e3, 1)
            out['vs_floor'] = round(bytes_accessed / HBM_BW / floor_s, 2)
        if flops:
            out['compute_bound_ms'] = round(flops / PEAK_BF16 * 1e3, 1)
        # HLO census: name the heavy traffic if any.
        hlo = compiled.as_text()
        ops = collections.Counter(
            m.group(1)
            for m in re.finditer(r'^\s*\S+ = \S+ (\w+)\(', hlo, re.M)
        )
        out['hlo_ops'] = {
            k: v for k, v in ops.most_common(12)
        }
        # Big tensors in copy/transpose ops (layout churn suspects).
        copies = re.findall(
            r'= (\S+) copy\(', hlo
        ) + re.findall(r'= (\S+) transpose\(', hlo)
        big = [c for c in copies if _tensor_bytes(c) > 50e6]
        out['big_copy_transposes'] = big[:8]
        mem = compiled.memory_analysis()
        if mem is not None:
            out['temp_gb'] = round(
                getattr(mem, 'temp_size_in_bytes', 0) / 1e9, 3
            )
        print(json.dumps(out), flush=True)


_DTYPE_BYTES = {'f32': 4, 'bf16': 2, 's32': 4, 'u32': 4, 's8': 1, 'u8': 1,
                'pred': 1, 'f16': 2, 's64': 8, 'u64': 8}


def _tensor_bytes(shape_str: str) -> float:
    m = re.match(r'(\w+?)\[([\d,]*)\]', shape_str)
    if not m:
        return 0.0
    dtype, dims = m.groups()
    n = 1
    for d in dims.split(','):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


if __name__ == '__main__':
    main()
