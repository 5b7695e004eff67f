"""The least bytes one decode step of a ``granitemoehybrid`` configuration
moves, computed from the configuration's file: the weights held on the chip
once (every layer's mixer, router, shared MLP and held experts, and the held
rows of the tied embedding as the output head), the recurrent state of the
rows that run read and written once each, and the K and V of their contexts
in the attention layers. bf16 weights and KV, float32 SSM state.
"""

from __future__ import annotations


def _dims(model: dict) -> dict:
    h = model['hidden_size']
    heads, p, n = model['mamba_n_heads'], model['mamba_d_head'], model['mamba_d_state']
    d_inner = heads * p
    conv_dim = d_inner + 2 * model['mamba_n_groups'] * n
    hd = h // model['num_attention_heads']
    return {
        'h': h, 'heads': heads, 'p': p, 'n': n, 'd_inner': d_inner,
        'conv_dim': conv_dim, 'k': model['mamba_d_conv'],
        'q_out': model['num_attention_heads'] * hd,
        'kv_out': model['num_key_value_heads'] * hd,
        'mamba_layers': model['layer_types'].count('mamba'),
        'attn_layers': model['layer_types'].count('attention'),
    }


def weight_params(model: dict) -> int:
    """Parameters one decode step reads: all that the chip holds (the
    embedding's held rows are the output head)."""
    d = _dims(model)
    h = d['h']
    mamba = (
        h * (d['d_inner'] + d['conv_dim'] + d['heads']) + d['d_inner'] * h
        + d['k'] * d['conv_dim'] + d['conv_dim'] + 3 * d['heads'] + d['d_inner']
    )
    attention = 2 * h * d['q_out'] + 2 * h * d['kv_out']
    mlp = (
        h * model.get('num_routed_experts', model['num_local_experts'])
        + 3 * h * model['shared_intermediate_size']
        + model['num_local_experts'] * 3 * h * model['intermediate_size']
        + 2 * h
    )
    layers = (
        d['mamba_layers'] * (mamba + mlp) + d['attn_layers'] * (attention + mlp)
    )
    return layers + model['vocab_size'] * h + h


def state_bytes_per_sequence(model: dict, dtype_bytes: int = 2) -> int:
    """Bytes of recurrent state one sequence holds: per Mamba layer the SSM
    state in float32 and ``d_conv - 1`` columns of the convolution's input."""
    d = _dims(model)
    ssm = d['heads'] * d['p'] * d['n'] * 4
    conv = (d['k'] - 1) * d['conv_dim'] * dtype_bytes
    return d['mamba_layers'] * (ssm + conv)


def kv_bytes_per_token(model: dict, dtype_bytes: int = 2) -> int:
    d = _dims(model)
    return 2 * d['attn_layers'] * d['kv_out'] * dtype_bytes


def decode_step_bytes(model: dict, rows: float, context_tokens: float) -> float:
    """Weights once, the state of ``rows`` sequences read and written, the
    K and V of ``context_tokens`` tokens (summed over the rows). The program
    rewrites the whole pool every step; the LEAST bytes count only the rows
    that run, so an emptier batch reads lower."""
    return float(
        2 * weight_params(model)
        + 2 * rows * state_bytes_per_sequence(model)
        + kv_bytes_per_token(model) * context_tokens
    )
