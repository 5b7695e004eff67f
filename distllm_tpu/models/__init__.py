"""Pure-JAX model implementations and HF-checkpoint loaders.

Models are functional: a pydantic config, an ``init(rng, config) -> params``
(random init, used in tests and benchmarks), an ``apply(params, batch, ...)``
pure function, a ``param_specs(config)`` pytree of PartitionSpecs for TP/DP
sharding, and a ``params_from_hf(state_dict, config)`` converter from
HuggingFace checkpoints. This replaces the reference's dependence on
``transformers.AutoModel`` forward passes (``distllm/embed/encoders/auto.py``)
with compiled, shardable JAX forwards.
"""

from __future__ import annotations


def decoder_families() -> dict:
    """``model_type -> (config_cls, module)`` for every decoder family.

    The single source of truth: the serving entry points dispatch through
    :func:`decoder_family`, and the embed auto-encoder builds its table
    from these rows plus the encoder-only families
    (``embed/encoders/auto.py``) — a new decoder lands in one place.
    """
    from distllm_tpu.models import (
        deepseek_v3,
        falcon_h1,
        gemma,
        granite_hybrid,
        laguna,
        lfm2,
        mistral,
        mixtral,
        ouro,
        sdar,
        smallthinker,
        solar_open2,
    )

    return {
        'mistral': (mistral.MistralConfig, mistral),
        'llama': (mistral.MistralConfig, mistral),
        'qwen2': (mistral.MistralConfig, mistral),
        'mixtral': (mixtral.MixtralConfig, mixtral),
        'gemma': (gemma.GemmaConfig, gemma),
        'gemma2': (gemma.GemmaConfig, gemma),
        'granitemoehybrid': (
            granite_hybrid.GraniteHybridConfig, granite_hybrid
        ),
        'laguna': (laguna.LagunaConfig, laguna),
        'deepseek_v3': (deepseek_v3.DeepseekV3Config, deepseek_v3),
        'lfm2_moe': (lfm2.Lfm2MoeConfig, lfm2),
        'falcon_h1': (falcon_h1.FalconH1Config, falcon_h1),
        'solar_open2': (solar_open2.SolarOpen2Config, solar_open2),
        'ouro': (ouro.OuroConfig, ouro),
        'smallthinker': (smallthinker.SmallThinkerConfig, smallthinker),
        'sdar_moe': (sdar.SdarConfig, sdar),
    }


def decoder_family(model_type: str):
    """(config_cls, module) for a DECODER checkpoint's HF ``model_type``.

    Encoder-only families (bert/esm/modernbert) are a loud error here,
    not a silent fall-through to the Mistral converter.
    """
    families = decoder_families()
    try:
        return families[model_type]
    except KeyError:
        raise ValueError(
            f'Unsupported decoder model_type {model_type!r}; '
            f'supported: {sorted(families)}'
        ) from None
