"""The least bytes one decode step of a ``falcon_h1`` configuration moves,
the bytes of its state update, and the bytes and operations its
paged-attention kernel is asked for, computed from the configuration's file.
A step reads the held layers' weights once and the output head once (the
embedding is a lookup of a row a token and is left out), the K and V pages of
the rows' whole contexts in EVERY layer, and reads and writes the recurrent
state of the rows that ran in every layer (the SSM state in float32, the
convolution's rows in the model's dtype). The sampler's passes over the
logits are not counted: every share reckoned from these is a floor.
bf16 weights and pages. ``tests/test_falcon_h1_cell.py`` holds
``weight_params`` to ``jax.eval_shape`` of the program's own
``init_on_device``.
"""

from __future__ import annotations


def layers(model: dict) -> int:
    return model['num_hidden_layers']


def conv_dim(model: dict) -> int:
    return (
        model['mamba_d_ssm']
        + 2 * model['mamba_n_groups'] * model['mamba_d_state']
    )


def mixer_params(model: dict) -> int:
    """One layer's Mamba-2 mixer: in-projection, taps and their bias,
    ``dt_bias``, ``A_log``, ``D``, the gated norm's scale, out-projection."""
    h, di, heads = model['hidden_size'], model['mamba_d_ssm'], model['mamba_n_heads']
    cd = conv_dim(model)
    return (
        h * (di + cd + heads) + model['mamba_d_conv'] * cd + cd
        + 3 * heads + di + di * h
    )


def layer_params(model: dict) -> int:
    h, d = model['hidden_size'], model['head_dim']
    q_out = model['num_attention_heads'] * d
    kv_out = model['num_key_value_heads'] * d
    attention = h * q_out + 2 * h * kv_out + q_out * h
    mlp = 3 * h * model['intermediate_size']
    return 2 * h + attention + mixer_params(model) + mlp


def held_params(model: dict) -> int:
    """All the chip holds: the layers, both ends of the vocabulary and the
    final norm."""
    h = model['hidden_size']
    return layers(model) * layer_params(model) + 2 * model['vocab_size'] * h + h


def weight_params(model: dict) -> int:
    """Parameters one decode step reads: the layers, the head and the
    final norm, not the embedding (a row a token)."""
    return held_params(model) - model['vocab_size'] * model['hidden_size']


def kv_bytes_per_token(model: dict, dtype_bytes: int = 2) -> int:
    """A token's pages: K and V, each counted once, of every layer."""
    row = model['num_key_value_heads'] * model['head_dim']
    return 2 * row * dtype_bytes * layers(model)


def kv_bytes(model: dict, tokens: float) -> float:
    return float(kv_bytes_per_token(model) * tokens)


def state_bytes_per_sequence(model: dict, dtype_bytes: int = 2) -> int:
    """A sequence's recurrent state over all layers: the SSM state in
    float32 and ``mamba_d_conv - 1`` rows of the convolution's input."""
    ssm = (
        model['mamba_n_heads'] * model['mamba_d_head'] * model['mamba_d_state'] * 4
    )
    conv = (model['mamba_d_conv'] - 1) * conv_dim(model) * dtype_bytes
    return layers(model) * (ssm + conv)


def attn_flops(model: dict, tokens: float) -> float:
    """Operations decode attention needs over ``tokens`` cached tokens: a
    query head's score against a key and its weighted sum of a value, a
    multiply and an add each over the head's dims, every query head (all 5
    of a KV head), every layer."""
    per_token_layer = 2 * model['num_attention_heads'] * 2 * model['head_dim']
    return float(per_token_layer * layers(model) * tokens)


def state_update_bytes(model: dict, state_rows: float, steps: float) -> float:
    """What the state update of ``steps`` decode steps moves at least: the
    state of the ``state_rows`` (row, step) pairs that ran once read and
    once written, and the mixers' weights once a step, bf16."""
    return (
        2.0 * state_bytes_per_sequence(model) * state_rows
        + 2.0 * layers(model) * mixer_params(model) * steps
    )


def decode_step_bytes(model: dict, rows: float, tokens: float) -> float:
    """Held layers and head once, the pages of the rows' contexts once, and
    the state of the ``rows`` that ran read and written."""
    return (
        2.0 * weight_params(model) + kv_bytes(model, tokens)
        + 2.0 * state_bytes_per_sequence(model) * rows
    )
