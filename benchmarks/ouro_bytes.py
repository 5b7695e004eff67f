"""The least bytes one decode step of an ``ouro`` configuration moves, and
the bytes and operations its paged-attention kernel is asked for, computed
from the configuration's file. A token runs the stack ``total_ut_steps``
times over the same weights, and nothing keeps 4.9 GB of them on the chip
between two passes, so a step reads the layers' weights ONCE A PASS, the
output head and the final norm and gate once (the embedding is a lookup of a
row a token and is left out), the K and V rows of the rows' whole contexts in
every plane (one a layer a pass: a pass reads its own planes alone, all of
them), and writes a K and a V row a plane for every row that ran. The
sampler's passes over the logits are not counted: every share reckoned from
these is a floor. bf16 weights and pages. ``tests/test_ouro_cell.py`` holds
``held_params`` to ``jax.eval_shape`` of the program's own ``init_on_device``.
"""

from __future__ import annotations


def passes(model: dict) -> int:
    return model['total_ut_steps']


def planes(model: dict) -> int:
    """K/V planes a token holds: one a layer a pass."""
    return model['num_hidden_layers'] * passes(model)


def head_dim(model: dict) -> int:
    return model.get('head_dim') or (
        model['hidden_size'] // model['num_attention_heads']
    )


def layer_params(model: dict) -> int:
    """One layer: q, k, v, o, the SwiGLU MLP and the four norms."""
    h, d = model['hidden_size'], head_dim(model)
    q_out = model['num_attention_heads'] * d
    kv_out = model['num_key_value_heads'] * d
    return (
        h * q_out + 2 * h * kv_out + q_out * h
        + 3 * h * model['intermediate_size'] + 4 * h
    )


def stack_params(model: dict) -> int:
    return model['num_hidden_layers'] * layer_params(model)


def end_params(model: dict) -> int:
    """What a step reads once beside the stack: the head, the final norm,
    the exit gate and its bias."""
    h = model['hidden_size']
    return model['vocab_size'] * h + h + h + 1


def held_params(model: dict) -> int:
    """All the chip holds: the stack, both ends of the vocabulary, the
    final norm and the gate."""
    return (
        stack_params(model) + end_params(model)
        + model['vocab_size'] * model['hidden_size']
    )


def step_weight_params(model: dict) -> int:
    """Parameters one decode step streams: the stack once a pass, the head,
    the final norm and the gate once."""
    return passes(model) * stack_params(model) + end_params(model)


def kv_bytes_per_token(model: dict, dtype_bytes: int = 2) -> int:
    """A token's rows: K and V, each counted once, of every plane."""
    row = model['num_key_value_heads'] * head_dim(model)
    return 2 * row * dtype_bytes * planes(model)


def kv_bytes(model: dict, tokens: float) -> float:
    return float(kv_bytes_per_token(model) * tokens)


def attn_flops(model: dict, tokens: float) -> float:
    """Operations decode attention needs over ``tokens`` cached tokens: a
    query head's score against a key and its weighted sum of a value, a
    multiply and an add each over the head's dims, every query head, every
    plane."""
    per_token_plane = 2 * model['num_attention_heads'] * 2 * head_dim(model)
    return float(per_token_plane * planes(model) * tokens)


def decode_step_bytes(model: dict, rows: float, tokens: float) -> float:
    """The stack's weights once a pass and the ends once, the K and V rows
    of the rows' contexts (``tokens`` summed over them) in every plane, and
    the rows written: a K and a V row a plane for each of the ``rows``."""
    return (
        2.0 * step_weight_params(model) + kv_bytes(model, tokens)
        + kv_bytes(model, rows)
    )
