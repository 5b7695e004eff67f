"""Encoder protocol and the shared JAX encoder runtime.

Reference parity: ``distllm/embed/encoders/base.py:14-55`` — an encoder owns
a tokenizer and produces ``[B, S, H]`` last hidden states. Here the forward
is a jitted pure function cached per bucket shape; params can be sharded over
a mesh for tensor parallelism (the reference's GPU equivalent relies on
``torch.compile`` + CUDA, ``auto.py:92-93``).
"""

from __future__ import annotations

from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from distllm_tpu.models.tokenizer import TokenBatch


@runtime_checkable
class Encoder(Protocol):
    config: object
    embedding_size: int

    @property
    def tokenizer(self): ...

    def forward(self, batch: TokenBatch) -> jnp.ndarray: ...


class JaxEncoder:
    """Concrete encoder driving a functional model's ``apply``.

    ``apply_fn(params, model_cfg, ids, mask) -> [B, S, H]`` is jitted once
    per input shape; bucketed tokenization keeps the set of shapes small.
    """

    def __init__(
        self,
        config,
        apply_fn,
        model_cfg,
        params,
        tokenizer,
        embedding_size: int,
        quantization: str | None = None,
    ) -> None:
        self.config = config
        self.model_cfg = model_cfg
        self._tokenizer = tokenizer
        self.embedding_size = embedding_size
        if quantization:
            # Weight-only quantization (reference: NF4 via bitsandbytes,
            # auto.py:46-56): store int8/nf4 codes in HBM; dequantization
            # happens per layer inside the jitted forward at the point of
            # use (common.dense unpacks QTensor leaves riding the layer
            # scan) — a whole-tree dequant before the forward would
            # materialize the full float model as HLO temps.
            from distllm_tpu.ops.quantization import quantize_pytree

            params = quantize_pytree(
                params,
                mode=quantization,
                out_dtype=getattr(model_cfg, 'dtype', 'bfloat16'),
            )
        self._apply = lambda p, ids, mask: apply_fn(p, model_cfg, ids, mask)
        self._forward = jax.jit(self._apply)
        self._pooled_cache: dict = {}
        self.params = params

    @property
    def tokenizer(self):
        return self._tokenizer

    @property
    def dtype(self):
        return jnp.dtype(getattr(self.model_cfg, 'dtype', 'float32'))

    def forward(self, batch: TokenBatch) -> jnp.ndarray:
        return self._forward(self.params, batch.input_ids, batch.attention_mask)

    def pooled_forward(self, pooler, normalize: bool = False):
        """Fused encode→pool(→normalize)→fp32 as ONE jitted dispatch.

        One dispatch per batch instead of two/three; XLA also fuses the
        pooling reduction into the final layer's epilogue instead of
        re-reading ``[B, S, H]``.
        Cached per (pooler type, pooler config, normalize): the closure
        captures the pooler instance, so a same-class pooler with different
        config must not reuse another instance's trace — but fresh
        same-config instances (one per work item in the embedding driver)
        MUST share it, or every file recompiles the fused graph.
        """
        pooler_cfg = getattr(pooler, 'config', None)
        cfg_key = (
            pooler_cfg.model_dump_json()
            if hasattr(pooler_cfg, 'model_dump_json')
            else repr(pooler_cfg)
        )
        key = (type(pooler).__qualname__, cfg_key, normalize)
        fused = self._pooled_cache.get(key)
        if fused is None:
            apply = self._apply

            def _fused(p, ids, mask):
                pooled = pooler.pool(apply(p, ids, mask), mask)
                if normalize:
                    pooled = pooled / jnp.clip(
                        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12
                    )
                return pooled.astype(jnp.float32)

            fused = jax.jit(_fused)
            self._pooled_cache[key] = fused

        def run(batch: TokenBatch) -> jnp.ndarray:
            return fused(self.params, batch.input_ids, batch.attention_mask)

        return run

    def shard(self, mesh, specs) -> None:
        """Place params on a mesh (TP/DP); jitted fns re-specialize lazily."""
        from distllm_tpu.parallel.sharding import shard_pytree

        self.params = shard_pytree(self.params, specs, mesh)

    def shutdown(self) -> None:
        """Release HBM references so a swapped-in model can fit."""
        self.params = None
        self._forward = None
        self._pooled_cache.clear()
