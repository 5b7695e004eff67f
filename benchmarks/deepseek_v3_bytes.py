"""The least bytes one decode step of a ``deepseek_v3`` configuration with
latent attention moves, and the bytes and operations its paged-attention
kernel is asked for, computed from the configuration's file: the weights held
on the chip once (every layer's attention, the dense layers' MLPs, every
sparse layer's router with its bias, shared experts and HELD experts, and the
held columns of the untied head; the embedding is gathered by row and left
out), and the latent rows of the rows' whole contexts AS STORED, once: a
cached token is one row a layer (``kv_lora_rank + qk_rope_head_dim`` values
in whole 128-lane tiles), keys and values at once, never counted for each.
bf16 weights and rows.
"""

from __future__ import annotations

LANES = 128


def _dense_layers(model: dict) -> int:
    return min(model['first_k_dense_replace'], model['num_hidden_layers'])


def weight_params(model: dict) -> int:
    """Parameters one decode step reads: all that the chip holds but the
    embedding."""
    h, heads = model['hidden_size'], model['num_attention_heads']
    rank, rope = model['kv_lora_rank'], model['qk_rope_head_dim']
    nope, v = model['qk_nope_head_dim'], model['v_head_dim']
    attention = (
        h * heads * (nope + rope)  # q_proj
        + h * (rank + rope) + rank  # kv_a_proj_with_mqa, kv_a_layernorm
        + rank * heads * (nope + v)  # kv_b_proj
        + heads * v * h + h  # o_proj, input norm
    )
    routed = model.get('num_routed_experts', model['n_routed_experts'])
    width = model['moe_intermediate_size']
    dense = 3 * h * model['intermediate_size'] + h
    sparse = (
        h * routed + routed  # router, selection bias
        + 3 * h * model['n_shared_experts'] * width
        + model['n_routed_experts'] * 3 * h * width + h
    )
    layers, first = model['num_hidden_layers'], _dense_layers(model)
    return (
        layers * attention + first * dense + (layers - first) * sparse
        + h * model['vocab_size'] + h
    )


def stored_row(model: dict) -> int:
    """Values a cached token's row takes in the pool: the latent and the
    rotated key head, in whole lane tiles."""
    row = model['kv_lora_rank'] + model['qk_rope_head_dim']
    return -(-row // LANES) * LANES


def row_bytes_per_token_layer(model: dict, dtype_bytes: int = 2) -> int:
    return stored_row(model) * dtype_bytes


def latent_bytes(model: dict, tokens: float) -> float:
    """Bytes of the stored rows behind ``tokens`` cached tokens (summed over
    the rows): every layer holds its own, and a row is keys and values at
    once."""
    return float(
        row_bytes_per_token_layer(model) * model['num_hidden_layers'] * tokens
    )


def attn_flops(model: dict, tokens: float) -> float:
    """Operations the absorbed decode attention needs over ``tokens`` cached
    tokens: a query head's score over the used row and its weighted sum of
    the latent, a multiply and an add each, every head, every layer."""
    rank = model['kv_lora_rank']
    per_token_layer = 2 * model['num_attention_heads'] * (
        rank + model['qk_rope_head_dim'] + rank
    )
    return float(per_token_layer * model['num_hidden_layers'] * tokens)


def decode_step_bytes(model: dict, tokens: float) -> float:
    """Held weights once and the stored rows of the rows' contexts once."""
    return 2.0 * weight_params(model) + latent_bytes(model, tokens)
