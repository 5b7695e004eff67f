"""Weight-only quantization for TPU inference.

Reference parity: the bitsandbytes NF4 4-bit load path
(``distllm/embed/encoders/auto.py:46-56``,
``distllm/generate/generators/huggingface_backend.py:66-77``). bitsandbytes is
CUDA-only; the TPU-native equivalent stores weights in HBM as int8
(per-output-channel symmetric) or nf4 (blockwise 4-bit normal-float codebook,
two codes packed per byte) and dequantizes to the compute dtype *inside* the
jitted forward — storage is 2x/4x smaller while the MXU still sees bf16.
Quantization itself runs once on host at load time (numpy), mirroring the
"quantize on load" semantics of ``BitsAndBytesConfig(load_in_4bit=True)``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# The 16 normal-float levels from the QLoRA NF4 data type: quantiles of a
# standard normal, normalized to [-1, 1]. Public constants.
NF4_CODEBOOK = np.array(
    [
        -1.0,
        -0.6961928009986877,
        -0.5250730514526367,
        -0.39491748809814453,
        -0.28444138169288635,
        -0.18477343022823334,
        -0.09105003625154495,
        0.0,
        0.07958029955625534,
        0.16093020141124725,
        0.24611230194568634,
        0.33791524171829224,
        0.44070982933044434,
        0.5626170039176941,
        0.7229568362236023,
        1.0,
    ],
    dtype=np.float32,
)


@jax.tree_util.register_pytree_node_class
class QTensor:
    """A quantized weight leaf: codes + scales + enough metadata to restore.

    Lives inside the params pytree in place of the float array; jit treats
    ``q``/``scale`` as traced children and the metadata as static, so the
    dequant lowers to a fused gather/multiply in the forward program.
    """

    def __init__(
        self,
        q: jnp.ndarray,
        scale: jnp.ndarray,
        kind: str,
        shape: tuple[int, ...],
        out_dtype: str,
        block_size: int = 0,
    ) -> None:
        self.q = q
        self.scale = scale
        self.kind = kind
        self.shape = tuple(shape)
        self.out_dtype = out_dtype
        self.block_size = block_size

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        return (self.q, self.scale), (
            self.kind,
            self.shape,
            self.out_dtype,
            self.block_size,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        kind, shape, out_dtype, block_size = aux
        return cls(q, scale, kind, shape, out_dtype, block_size)

    # -- numerics --------------------------------------------------------
    def dequantize(self) -> jnp.ndarray:
        """Restore the float weight.

        Works both on the whole leaf AND on a ``lax.scan``-sliced view: when
        a stacked ``[L, ...]`` QTensor rides a scan over layers, scan slices
        the ``q``/``scale`` children (dropping the leading dim) while the
        static ``shape`` metadata still describes the full stack — so the
        target shape is derived from the *children's* runtime shapes, using
        ``self.shape`` only for the trailing dims. Scanning the quantized
        tree is what lets dequantization happen per layer inside the layer
        scan: dequantizing the full 7B stack outside the scan materializes
        ~13 GiB of bf16 HLO temps and OOMs a 16 GiB chip (seen once on
        older code, 2026-07-31 notes in git history; not re-checked).
        """
        if self.kind == 'int8':
            # q keeps the weight's own shape (sliced or not); scale is
            # keepdims-broadcastable against it.
            return self.q.astype(self.out_dtype) * self.scale.astype(
                self.out_dtype
            )
        if self.kind == 'nf4':
            # Packed codes: [..., nblocks, block_size // 2]; scale
            # [..., nblocks]. Leading stack dims (if still present) pass
            # through untouched.
            high = (self.q >> 4) & 0x0F
            low = self.q & 0x0F
            codes = jnp.stack([high, low], axis=-1).reshape(
                *self.q.shape[:-1], -1
            )
            codebook = jnp.asarray(NF4_CODEBOOK, dtype=self.out_dtype)
            values = codebook[codes] * self.scale.astype(self.out_dtype)[
                ..., None
            ]
            # The core weight is always 2-D; any dims of `q` before its
            # last two ([..., nblocks, packed]) are stack dims that pass
            # through (present when unsliced, gone when scan-sliced).
            lead_dims = self.q.shape[:-2]
            weight_tail = self.shape[-2:]
            tail_elems = int(np.prod(weight_tail))
            flat = values.reshape(*lead_dims, -1)[..., :tail_elems]
            return flat.reshape(*lead_dims, *weight_tail)
        raise ValueError(f'unknown quantization kind {self.kind!r}')

    @property
    def nbytes(self) -> int:
        return int(self.q.size * self.q.dtype.itemsize) + int(
            self.scale.size * self.scale.dtype.itemsize
        )


def quantize_int8(w: np.ndarray, out_dtype: str = 'bfloat16') -> QTensor:
    """Symmetric per-output-channel int8 (channel = last axis).

    For stacked-layer kernels ``[L, in, out]`` (``common.stack_layers``) the
    scale is per ``(layer, channel)`` — each layer keeps its own dynamic
    range. ``q`` keeps the original shape; ``scale`` is keepdims-broadcastable
    so dequant is a single fused multiply.
    """
    w = np.asarray(w, dtype=np.float32)
    # Reduce ONLY the input dim (second-to-last): every leading dim —
    # layer stack [L, in, out], expert banks [L, E, in, out] — keeps its
    # own per-channel dynamic range.
    reduce_axes = (w.ndim - 2,)
    absmax = np.abs(w).max(axis=reduce_axes, keepdims=True)
    scale = (absmax / 127.0).astype(np.float32)
    scale = np.where(scale == 0.0, 1.0, scale)
    q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    return QTensor(
        jnp.asarray(q), jnp.asarray(scale), 'int8', w.shape, out_dtype
    )


def quantize_nf4(
    w: np.ndarray, block_size: int = 64, out_dtype: str = 'bfloat16'
) -> QTensor:
    """Blockwise NF4: per-block absmax scale + 4-bit codebook codes.

    Codes are packed two per uint8 (high nibble first). Blocks run over the
    flattened weight; a partial tail block is zero-padded (zero maps to code
    7, exactly representable, so padding adds no error).
    """
    w = np.asarray(w, dtype=np.float32)
    # Stacked [L, in, out] kernels pack per layer ([L, nblocks, packed]) so
    # the leading dim survives — a lax.scan over layers can slice the codes
    # and dequantize ONE layer at a time inside the loop body (see
    # QTensor.dequantize).
    lead = w.shape[:-2] if w.ndim >= 3 else ()
    flat = w.reshape(*lead, -1)
    pad = (-flat.shape[-1]) % block_size
    if pad:
        flat = np.concatenate(
            [flat, np.zeros((*lead, pad), dtype=np.float32)], axis=-1
        )
    blocks = flat.reshape(*lead, -1, block_size)
    absmax = np.abs(blocks).max(axis=-1)
    scale = np.where(absmax == 0.0, 1.0, absmax).astype(np.float32)
    normalized = blocks / scale[..., None]
    # Nearest codebook level via searchsorted on the midpoints between
    # adjacent levels — same result as argmin(|x - codebook|) without the
    # 16x host-memory blowup (a 7B stacked kernel is ~2e9 elements).
    midpoints = (NF4_CODEBOOK[1:] + NF4_CODEBOOK[:-1]) / 2.0
    idx = np.searchsorted(midpoints, normalized).astype(np.uint8)
    packed = (idx[..., 0::2] << 4) | idx[..., 1::2]
    return QTensor(
        jnp.asarray(packed),
        jnp.asarray(scale),
        'nf4',
        w.shape,
        out_dtype,
        block_size,
    )


def _should_quantize(path: tuple, leaf: Any, min_size: int) -> bool:
    # Linear kernels are 2-D [in, out], stacked-per-layer 3-D [L, in, out]
    # (models/common.py stack_layers), or stacked expert banks 4-D
    # [L, E, in, out] (models/mixtral.py); anything else stays float.
    if (
        not hasattr(leaf, 'ndim')
        or leaf.ndim not in (2, 3, 4)
        or leaf.size < min_size
        or not jnp.issubdtype(leaf.dtype, jnp.floating)
    ):
        return False
    keys = '/'.join(str(getattr(k, 'key', k)) for k in path).lower()
    # Embedding tables, norm scales, biases, the output head, and MoE
    # router kernels stay full precision (bnb quantizes only nn.Linear
    # weights and exempts lm_head via llm_int8_skip_modules; routers are
    # tiny [H, E] and routing is precision-sensitive). Stacked biases
    # are 2-D [L, out], hence the name gate rather than an ndim gate.
    return not any(
        tag in keys
        for tag in ('embed', 'norm', 'ln', 'bias', 'head', 'router')
    )


def normalize_mode(value: bool | str | None) -> str | None:
    """Coerce a config's ``quantization`` field to a mode string.

    ``True`` means ``'nf4'`` — the reference's quantization flag loads
    bitsandbytes NF4 (``auto.py:46-56``); ``False``/``None``/``''`` disable.
    """
    if value is True:
        return 'nf4'
    return value or None


def quantize_pytree(
    params: Any,
    mode: str = 'nf4',
    min_size: int = 4096,
    block_size: int = 64,
    out_dtype: str = 'bfloat16',
    delete_source: bool = False,
) -> Any:
    """Replace large 2-D float leaves with :class:`QTensor`.

    ``mode`` is ``'int8'`` or ``'nf4'``. Embedding/norm leaves and small
    tensors are left untouched.

    ``delete_source=True`` streams the conversion: each replaced device
    leaf is copied to host and **deleted before its quantized replacement
    is materialized**, so device memory peaks at the unquantized weights
    and then decreases monotonically. Without it, quantizing a 7B bf16
    model (13.5 GiB) would hold source + codes (~20.5 GiB) simultaneously
    — past a 16 GiB v5e's HBM. Only set it when the caller owns ``params``
    (the source leaves become unusable).
    """
    if mode not in ('int8', 'nf4'):
        raise ValueError(f'unknown quantization mode {mode!r}')

    def _quantize(path, leaf):
        if isinstance(leaf, QTensor) or not _should_quantize(
            path, leaf, min_size
        ):
            return leaf
        # An owned fp32 copy, not np.asarray: on some backends asarray of a
        # jax.Array is a zero-copy view into the device/host buffer, which
        # delete() below would free out from under the quantizer.
        host = np.array(leaf, dtype=np.float32, copy=True)
        if delete_source and isinstance(leaf, jax.Array):
            leaf.delete()
        if mode == 'int8':
            return quantize_int8(host, out_dtype)
        return quantize_nf4(host, block_size, out_dtype)

    return jax.tree_util.tree_map_with_path(
        _quantize, params, is_leaf=lambda x: isinstance(x, QTensor)
    )


def quantize_pytree_abstract(
    shapes: Any,
    mode: str = 'int8',
    min_size: int = 4096,
    make_leaf=None,
    out_dtype: str = 'bfloat16',
) -> Any:
    """Shape-level analogue of :func:`quantize_pytree` for AOT compiles.

    Maps a tree of ``ShapeDtypeStruct``-like leaves to the pytree the real
    quantizer would produce — same quantize-or-pass-through policy, same
    code/scale shapes — without any data. ``make_leaf(shape, dtype)``
    constructs abstract leaves (defaults to ``jax.ShapeDtypeStruct``).
    Keeping this NEXT TO the quantizer means compile-only preflights and
    CI lowering tests can't drift from the layout serving actually runs.
    Currently int8 only (the AOT-validated serving mode). ``out_dtype``
    must match what the real quantizer is called with (the engine passes
    the model dtype) or the compiled program diverges from serving.
    """
    if mode != 'int8':
        raise NotImplementedError(f'abstract quantization for {mode!r}')
    if make_leaf is None:
        def make_leaf(shape, dtype):
            return jax.ShapeDtypeStruct(tuple(shape), dtype)

    def convert(path, leaf):
        if isinstance(leaf, QTensor):
            return leaf
        if not _should_quantize(path, leaf, min_size):
            return make_leaf(leaf.shape, leaf.dtype)
        shape = tuple(leaf.shape)
        # Mirrors quantize_int8: only the input dim (second-to-last)
        # reduces, keepdims — [1, out] for 2-D, [L, 1, out] for stacked
        # 3-D, [L, E, 1, out] for expert banks.
        scale_shape = (*shape[:-2], 1, shape[-1])
        return QTensor(
            make_leaf(shape, jnp.int8),
            make_leaf(scale_shape, jnp.float32),
            'int8',
            shape,
            out_dtype,
        )

    return jax.tree_util.tree_map_with_path(
        convert, shapes, is_leaf=lambda x: isinstance(x, QTensor)
    )


def dequantize_pytree(params: Any) -> Any:
    """Restore float arrays from :class:`QTensor` leaves (jit-safe)."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.dequantize() if isinstance(leaf, QTensor) else leaf,
        params,
        is_leaf=lambda leaf: isinstance(leaf, QTensor),
    )


def quantized_nbytes(params: Any) -> tuple[int, int]:
    """(quantized_bytes, float_bytes) over the pytree — for telemetry."""
    q_bytes = 0
    f_bytes = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QTensor)
    ):
        if isinstance(leaf, QTensor):
            q_bytes += leaf.nbytes
        else:
            f_bytes += int(leaf.size * leaf.dtype.itemsize)
    return q_bytes, f_bytes
