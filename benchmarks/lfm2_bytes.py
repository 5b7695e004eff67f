"""The least bytes one decode step of an ``lfm2_moe`` configuration moves,
and the bytes and operations its paged-attention kernel is asked for,
computed from the configuration's file: the weights held on the chip once
(every conv and attention mixer, the dense layers' MLPs, every sparse
layer's router with its bias and HELD experts, the norms, and the embedding
once, as the output head it also is; its gather by row is left out), the K
and V pages of the rows' whole contexts in the attention layers alone, and
the conv layers' state of the rows that ran, read and written. bf16 weights,
pages and state. ``tests/test_lfm2_cell.py`` holds ``weight_params`` to
``jax.eval_shape`` of the program's own ``init_on_device``.
"""

from __future__ import annotations


def head_dim(model: dict) -> int:
    return model['hidden_size'] // model['num_attention_heads']


def layer_counts(model: dict) -> dict:
    """Layers of each kind: conv and attention mixers, dense and sparse
    MLPs."""
    types = list(model['layer_types'])
    dense = min(model['num_dense_layers'], len(types))
    return {
        'conv': types.count('conv'), 'attn': len(types) - types.count('conv'),
        'dense': dense, 'sparse': len(types) - dense,
    }


def weight_params(model: dict) -> int:
    """Parameters one decode step reads: all that the chip holds."""
    h, d = model['hidden_size'], head_dim(model)
    q_out = model['num_attention_heads'] * d
    kv_out = model['num_key_value_heads'] * d
    conv = h + h * 3 * h + model['conv_L_cache'] * h + h * h
    attn = h + h * q_out + 2 * h * kv_out + 2 * d + q_out * h
    dense = h + 3 * h * model['intermediate_size']
    routed = model.get('num_routed_experts', model['num_experts'])
    sparse = (
        h + h * routed + routed  # ffn_norm, router, expert_bias
        + model['num_experts'] * 3 * h * model['moe_intermediate_size']
    )
    n = layer_counts(model)
    return (
        n['conv'] * conv + n['attn'] * attn + n['dense'] * dense
        + n['sparse'] * sparse + model['vocab_size'] * h + h
    )


def kv_bytes_per_token(model: dict, dtype_bytes: int = 2) -> int:
    """A token's pages: K and V, each counted once, of every ATTENTION
    layer (the conv layers hold no pages)."""
    row = model['num_key_value_heads'] * head_dim(model)
    return 2 * row * dtype_bytes * layer_counts(model)['attn']


def kv_bytes(model: dict, tokens: float) -> float:
    """Bytes of the pages behind ``tokens`` cached tokens (summed over the
    rows)."""
    return float(kv_bytes_per_token(model) * tokens)


def state_bytes_per_sequence(model: dict, dtype_bytes: int = 2) -> int:
    """A sequence's conv state: ``conv_L_cache - 1`` rows a conv layer."""
    return (
        layer_counts(model)['conv'] * (model['conv_L_cache'] - 1)
        * model['hidden_size'] * dtype_bytes
    )


def attn_flops(model: dict, tokens: float) -> float:
    """Operations decode attention needs over ``tokens`` cached tokens: a
    query head's score against a key and its weighted sum of a value, a
    multiply and an add each over the head's dims, every query head (all 4
    of a KV head), every attention layer."""
    per_token_layer = 2 * model['num_attention_heads'] * 2 * head_dim(model)
    return float(per_token_layer * layer_counts(model)['attn'] * tokens)


def decode_step_bytes(model: dict, rows: float, tokens: float) -> float:
    """Held weights once, the pages of the rows' contexts once, and the
    conv state of the ``rows`` that ran read and written."""
    return (
        2.0 * weight_params(model) + kv_bytes(model, tokens)
        + 2.0 * state_bytes_per_sequence(model) * rows
    )
