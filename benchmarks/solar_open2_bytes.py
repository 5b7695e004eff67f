"""What a ``solar_open2`` configuration's chip holds and the least it has to
move and do, computed from the configuration's file alone (shapes, never an
implementation): the parameters held and those a decode step reads, a
sequence's state and pages, the bytes of a whole decode step, and the
operations and bytes of the two forms of the Kimi-delta recurrence, the span
form (prefill) and the step update (decode), projections left out. Every
share reckoned from these is a floor: the sampler's passes over the logits,
the router's top-k and the convolutions are not counted. bf16 weights and
pages, a float32 matrix state. ``tests/test_solar_open2.py`` holds
``held_params`` to ``jax.eval_shape`` of the program's own ``init_on_device``.
"""

from __future__ import annotations


def layers(model: dict) -> int:
    return model['num_hidden_layers']


def gqa_layers(model: dict) -> int:
    return sum(1 for li in range(layers(model)) if li in model['gqa_layers'])


def kda_layers(model: dict) -> int:
    return layers(model) - gqa_layers(model)


def _kda(model: dict) -> tuple[int, int, int]:
    """``(heads, head dim, taps)`` of a KDA layer."""
    linear = model['linear_attn_config']
    return linear['num_heads'], linear['head_dim'], linear['short_conv_kernel_size']


def kda_mixer_params(model: dict) -> int:
    """One KDA mixer: q, k, v, o; the taps; the decay's low-rank pair,
    ``A_log`` and ``dt_bias``; beta; the gate's low-rank pair and bias; the
    head norm's scale; the input norm."""
    h = model['hidden_size']
    heads, d, taps = _kda(model)
    wide, rank = heads * d, d
    return (
        4 * h * wide + taps * 3 * wide
        + (h * rank + rank * wide + heads + wide)
        + h * heads
        + (h * rank + rank * wide + wide)
        + d + h
    )


def gqa_mixer_params(model: dict) -> int:
    """One gated attention mixer: q, the gate and o, k and v, the norm."""
    h, d = model['hidden_size'], model['head_dim']
    q_out = model['num_attention_heads'] * d
    kv_out = model['num_key_value_heads'] * d
    return 3 * h * q_out + 2 * h * kv_out + h


def expert_params(model: dict) -> int:
    return 3 * model['hidden_size'] * model['moe_intermediate_size']


def moe_params(model: dict, experts: int | None = None) -> int:
    """One layer's experts' block with ``experts`` routed experts held (the
    configuration's share by default): the banks, the shared expert, the
    router over ALL routed experts with its selection bias, the norm."""
    held = model['n_routed_experts'] if experts is None else experts
    routed = model.get('num_routed_experts', model['n_routed_experts'])
    h = model['hidden_size']
    return (held + 1) * expert_params(model) + h * routed + routed + h


def held_params(model: dict) -> int:
    """All the chip holds: the layers, both ends of its vocabulary slice
    and the final norm."""
    h = model['hidden_size']
    return (
        kda_layers(model) * kda_mixer_params(model)
        + gqa_layers(model) * gqa_mixer_params(model)
        + layers(model) * moe_params(model)
        + 2 * model['vocab_size'] * h + h
    )


def weight_params(model: dict) -> int:
    """Parameters one decode step reads: everything held but the embedding
    (a row a token)."""
    return held_params(model) - model['vocab_size'] * model['hidden_size']


def kv_bytes_per_token(model: dict, dtype_bytes: int = 2) -> int:
    """A token's pages: K and V, each counted once, of the attention
    layers."""
    row = model['num_key_value_heads'] * model['head_dim']
    return 2 * row * dtype_bytes * gqa_layers(model)


def kv_bytes(model: dict, tokens: float) -> float:
    return float(kv_bytes_per_token(model) * tokens)


def matrix_state_bytes(model: dict) -> int:
    """One KDA layer's matrix state of a sequence: ``[H, d_k, d_v]``
    float32."""
    heads, d, _ = _kda(model)
    return heads * d * d * 4


def state_bytes_per_sequence(model: dict, dtype_bytes: int = 2) -> int:
    """A sequence's state over the KDA layers: the matrix state and ``K -
    1`` rows of the three convolutions' inputs."""
    heads, d, taps = _kda(model)
    conv = (taps - 1) * 3 * heads * d * dtype_bytes
    return kda_layers(model) * (matrix_state_bytes(model) + conv)


def attn_flops(model: dict, tokens: float) -> float:
    """Operations decode attention needs over ``tokens`` cached tokens: a
    query head's score against a key and its weighted sum of a value, a
    multiply and an add each over the head's dims, every query head (all 8
    of a KV head), the attention layers."""
    per_token_layer = 2 * model['num_attention_heads'] * 2 * model['head_dim']
    return float(per_token_layer * gqa_layers(model) * tokens)


# ------------------------------------------------ the Kimi-delta recurrence
def kda_step_bytes(model: dict, state_rows: float) -> float:
    """What the step update of ``state_rows`` (row, step) pairs moves at
    least: each pair's matrix state of every KDA layer once read and once
    written, float32. Its inputs (q, k, v, g: 4 x H x d a pair) are a
    hundredth of that and left out."""
    return 2.0 * kda_layers(model) * matrix_state_bytes(model) * state_rows


def kda_step_flops(model: dict, state_rows: float) -> float:
    """Operations of the step update: the decay (1 a state element), ``S^T
    k`` (2), the rank-one correction (2) and ``S^T q`` (2)."""
    heads, d, _ = _kda(model)
    return 7.0 * heads * d * d * kda_layers(model) * state_rows


def kda_span_flops(model: dict, tokens: float) -> float:
    """Operations the span recurrence cannot do without, a token, a head:
    what the recurrence itself spends on it (``kda_step_flops``: 7 d_k
    d_v). A chunk form trades state traffic for arithmetic and may spend
    more; this is the work it stands for."""
    return kda_step_flops(model, tokens)


def kda_span_bytes(
    model: dict, tokens: float, spans: float, dtype_bytes: int = 2
) -> float:
    """Bytes the span recurrence cannot do without: its inputs q, k, v, g
    and its output (5 x H x d a token) once each in the model's dtype, and
    every row's float32 matrix state read and written once a span."""
    heads, d, _ = _kda(model)
    return kda_layers(model) * (
        5.0 * heads * d * dtype_bytes * tokens
        + 2.0 * matrix_state_bytes(model) * spans
    )


def decode_step_bytes(model: dict, rows: float, tokens: float) -> float:
    """Held layers and head once, the pages of the rows' contexts once, and
    the state of the ``rows`` that ran read and written."""
    return (
        2.0 * weight_params(model) + kv_bytes(model, tokens)
        + 2.0 * state_bytes_per_sequence(model) * rows
    )
