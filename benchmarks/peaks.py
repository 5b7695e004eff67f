"""Published peaks by ``device_kind``, and the operations and bytes that the
work needs, computed from shapes. A device that is not in the table is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip. (Copied from
``distllm_tpu/observability/roofline.py DEVICE_PEAKS``; the benchmark keeps
its own copy so that a later PR cannot move the yardstick.)
"""

from __future__ import annotations

# device_kind -> (bf16 FLOP/s, HBM bytes/s, HBM bytes)
DEVICE_PEAKS: dict[str, tuple[float, float, float]] = {
    'TPU v5 lite': (197e12, 819e9, 16e9),
    'TPU v5e': (197e12, 819e9, 16e9),
}


def device_peaks(device_kind: str) -> tuple[float, float, float]:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f'device kind {device_kind!r} is not in benchmarks/peaks.py '
            f'DEVICE_PEAKS ({sorted(DEVICE_PEAKS)}); add it with its source'
        ) from None


def encoder_flops(model: dict, tokens_real: int, sum_sq_len: int) -> float:
    """Matmul FLOPs a BERT-style encoder needs for sequences holding
    ``tokens_real`` tokens in all, whose squared lengths sum to
    ``sum_sq_len``: per token and layer the Q, K, V, O projections (4 h^2
    multiply-adds) and the MLP (2 h i); per sequence and layer the scores and
    the weighted sum (2 S^2 h multiply-adds). Padding does no needed work.
    Embedding lookups, norms and the pooler are not counted."""
    h = model['hidden_size']
    i = model['intermediate_size']
    layers = model['num_hidden_layers']
    per_token = 2 * (4 * h * h + 2 * h * i)
    attention = 2 * 2 * h * sum_sq_len
    return float(layers * (per_token * tokens_real + attention))


def decoder_weight_bytes(model: dict, bytes_per_param: int = 2) -> float:
    """Bytes of weights one decode step has to read: every layer's attention
    and MLP matrices and the output head. The embedding table is gathered by
    row, not streamed, and is left out."""
    h = model['hidden_size']
    i = model['intermediate_size']
    hd = model.get('head_dim') or h // model['num_attention_heads']
    q_out = model['num_attention_heads'] * hd
    kv_out = model['num_key_value_heads'] * hd
    per_layer = h * q_out + 2 * h * kv_out + q_out * h + 3 * h * i
    head = h * model['vocab_size']
    return float(bytes_per_param * (model['num_hidden_layers'] * per_layer + head))


def decoder_kv_bytes_per_token(model: dict, bytes_per_value: int = 2) -> float:
    """Bytes of K and V that one cached token holds over all layers."""
    hd = model.get('head_dim') or (
        model['hidden_size'] // model['num_attention_heads']
    )
    return float(
        2 * model['num_hidden_layers'] * model['num_key_value_heads'] * hd
        * bytes_per_value
    )


def decode_step_bytes(model: dict, context_tokens: float) -> float:
    """Least bytes one decode step moves: the weights once, and the K and V of
    every token in the batch's contexts (``context_tokens`` summed over the
    sequences of the step)."""
    return decoder_weight_bytes(model) + (
        decoder_kv_bytes_per_token(model) * context_tokens
    )
