"""TPU generator: the in-process paged-KV engine behind the LLMGenerator API.

Reference parity: ``distllm/generate/generators/vllm_backend.py`` — same
config surface (model path, temperature, ``top_p`` XOR ``min_p``,
``max_tokens``, ``tensor_parallel_size``) but the backend is our own
JAX/Pallas engine instead of vLLM. Registered under both ``tpu`` and
``vllm`` names so reference YAML configs keep working.
"""

from __future__ import annotations

from typing import Literal

from pydantic import Field, model_validator

from distllm_tpu.generate.engine import EngineConfig, LLMEngine, SamplingParams
from distllm_tpu.ops.quantization import normalize_mode, quantize_pytree
from distllm_tpu.utils import BaseConfig


class TpuGeneratorConfig(BaseConfig):
    name: Literal['tpu', 'vllm'] = 'tpu'
    pretrained_model_name_or_path: str = Field(
        description='Local path to an HF-format decoder checkpoint.'
    )
    tokenizer_name: str | None = None
    trust_remote_code: bool = False
    temperature: float = 0.5
    min_p: float = 0.1
    top_p: float = 0.0
    max_tokens: int = 2000
    tensor_parallel_size: int = Field(
        default=1, description='TP degree over the mesh model axis.'
    )
    # Engine capacity knobs (vLLM analogues).
    block_size: int = 16
    num_blocks: int = 2048
    max_num_seqs: int = 16
    max_model_len: int = 4096
    quantization: bool | Literal['int8', 'nf4'] = Field(
        default=False,
        description='Weight-only quantized serving; True means nf4 (the '
        "reference's bitsandbytes NF4 option).",
    )
    # Serving perf knobs (production configs must be able to turn on what
    # the measured numbers used).
    # Defaults are None = inherit EngineConfig's documented defaults, so
    # one place owns each default and reference-parity semantics (exact
    # full-vocab sampling) hold unless a config opts in.
    attn_backend: str = Field(
        default='auto',
        description="Paged-attention kernel selector: 'auto' = the fused "
        'ragged Pallas kernel when the chip, head_dim, and KV geometry '
        "support it, XLA otherwise; 'interpret' runs the kernel on the "
        'Pallas interpreter (CPU parity tier). Validated against '
        'ops.paged_attention.ATTN_BACKENDS — the single owner of the '
        'selector set (docs/serving.md "Attention kernel backends").',
    )
    decode_steps: int | None = Field(
        default=None,
        ge=1,
        description='Tokens per fused decode dispatch (amortizes the '
        'host round trip; 1 restores per-token dispatch).',
    )
    sampling_top_window: int | None = Field(
        default=None,
        ge=0,
        description='Rank cap on every request: keep the tokens no smaller '
        'than the K-th largest logit, before top-p (0 = no cap). Not a '
        'speed setting: the sampler sorts nothing at any K.',
    )
    enable_prefix_cache: bool | None = Field(
        default=None,
        description='Automatic prefix caching: reuse KV blocks across '
        'requests sharing a block-aligned prompt prefix (RAG system '
        'prompts, MCQA stems) — prefill runs only on the uncached tail.',
    )
    prefill_chunk_tokens: int | None = Field(
        default=None,
        ge=0,
        description='Split uncached prefill tails longer than this into '
        'sequential chunks so one long prompt cannot stall decode '
        '(0 disables chunking).',
    )
    enable_mixed_batching: bool | None = Field(
        default=None,
        description='Mixed prefill+decode serving windows: cache-hit '
        'tails and chunked prefill spans ride INSIDE the fused decode '
        'dispatches instead of serializing between them '
        '(docs/serving.md). Token-identical under greedy sampling.',
    )
    max_window_prefill_tokens: int | None = Field(
        default=None,
        ge=0,
        description='Prefill-chunk token budget one mixed window may '
        'carry (each token bucket is one extra compiled window shape).',
    )
    draft_k: int | None = Field(
        default=None,
        ge=0,
        description='Prompt-lookup speculative decoding: draft up to '
        'this many tokens per row from the row\'s own history and '
        'verify them in one ragged dispatch — every accepted token '
        'skipped a weight pass (docs/speculative.md). Greedy rows '
        'verify by argmax comparison; temperature > 0 rows verify by '
        'device-side rejection sampling ("Sampled verification"); '
        '0 disables.',
    )
    spec_ngram: int | None = Field(
        default=None,
        ge=1,
        description='n-gram length the prompt-lookup drafter matches on.',
    )
    # Resilience knobs (docs/resilience.md). None = inherit
    # EngineConfig's defaults; the chat server defaults the deadline and
    # retry budget ON (ChatAppConfig.build_generator) — a serving
    # replica must degrade per-request, not per-process.
    ttft_slo_s: float | None = Field(
        default=None,
        ge=0,
        description='TTFT service-level objective in seconds (SLO/goodput '
        'accounting; the shed threshold when admission_control is on). '
        '0 disables.',
    )
    request_deadline_s: float | None = Field(
        default=None,
        ge=0,
        description='Per-request wall-clock deadline: a stuck request '
        'finishes with finish_reason="timeout" and frees its KV blocks '
        'instead of holding them forever. 0 disables.',
    )
    max_dispatch_retries: int | None = Field(
        default=None,
        ge=0,
        description='Crash-domain recovery: retry a failed window this '
        'many times (bounded backoff) before quarantining the involved '
        'requests to FAILED with a recorded error. 0 = propagate the '
        'first dispatch exception (the offline/batch contract).',
    )
    admission_control: bool | None = Field(
        default=None,
        description='SLO-aware shedding: predict TTFT at enqueue and '
        'refuse (EngineOverloaded -> HTTP 429 + Retry-After) requests '
        'whose prediction busts ttft_slo_s, instead of queueing them '
        'into guaranteed misses. Requires ttft_slo_s > 0.',
    )

    @model_validator(mode='after')
    def _attn_backend_in_catalog(self) -> 'TpuGeneratorConfig':
        # Membership over a Literal copy: the selector set has ONE owner
        # (instruments.ATTN_BACKEND_LABELS -> ops.ATTN_BACKENDS), so a
        # new kernel tier is reachable here without touching this file.
        from distllm_tpu.ops.paged_attention import ATTN_BACKENDS

        if self.attn_backend not in ATTN_BACKENDS:
            raise ValueError(
                f'attn_backend must be one of {ATTN_BACKENDS}, '
                f'got {self.attn_backend!r}'
            )
        return self

    @model_validator(mode='after')
    def _xor_top_p_min_p(self) -> 'TpuGeneratorConfig':
        # Reference behavior (vllm_backend.py:48-60): an explicitly set
        # top_p wins and min_p is ignored; min_p (default 0.1) applies
        # otherwise. A reference config carrying only `top_p: 0.95` must
        # load unchanged — min_p's own default cannot veto it. Only a
        # config that EXPLICITLY sets both truthy values is ambiguous.
        if self.top_p and self.min_p:
            if 'min_p' in self.model_fields_set:
                raise ValueError('Only one of top_p or min_p can be set')
            self.min_p = 0.0
        return self


def _generation_config_eos(model_dir: str) -> tuple[int, ...]:
    """ALL ``eos_token_id`` values from the checkpoint's
    generation_config.json (int or list — vLLM honors every entry, e.g.
    gemma-2-it stops on both <eos> and <end_of_turn>). Empty tuple on a
    missing/malformed file — startup must fall back, never crash."""
    import json
    from pathlib import Path

    path = Path(model_dir) / 'generation_config.json'
    if not path.exists():
        return ()
    try:
        eos = json.loads(path.read_text()).get('eos_token_id')
        ids = eos if isinstance(eos, list) else [eos]
        return tuple(int(i) for i in ids if i is not None)
    except (OSError, ValueError, TypeError, AttributeError):
        return ()


class TpuGenerator:
    def __init__(self, config: TpuGeneratorConfig) -> None:
        import jax

        from distllm_tpu.models import decoder_family
        from distllm_tpu.models.loader import read_checkpoint, read_hf_config
        from distllm_tpu.models.tokenizer import HFTokenizer
        from distllm_tpu.parallel.mesh import MeshSpec, make_mesh
        from distllm_tpu.parallel.sharding import shard_pytree

        self.config = config
        hf_cfg = read_hf_config(config.pretrained_model_name_or_path)
        # Dispatch on the checkpoint's model_type (the vLLM analogue of
        # serving any supported architecture from one backend): the
        # Mistral module covers mistral/llama/qwen2; Mixtral adds the
        # MoE expert banks — both serve through the same engine.
        cfg_cls, family = decoder_family(hf_cfg.get('model_type', 'mistral'))
        model_cfg = cfg_cls.from_hf_config(hf_cfg)
        params = family.params_from_hf(
            read_checkpoint(config.pretrained_model_name_or_path), model_cfg
        )
        quant_mode = normalize_mode(config.quantization)
        if quant_mode:
            # Quantize BEFORE sharding so codes are placed once (QTensor
            # leaves replicate; float leaves take their TP specs).
            params = quantize_pytree(
                params, mode=quant_mode, out_dtype=model_cfg.dtype
            )
        mesh = None
        if config.tensor_parallel_size > 1:
            mesh = make_mesh(
                MeshSpec(data=1, model=config.tensor_parallel_size),
                devices=jax.devices()[: config.tensor_parallel_size],
            )
            params = shard_pytree(
                params, family.param_specs(model_cfg, params), mesh
            )
        # The context limit is the engine's, not the tokenizer file's: a
        # decoder checkpoint's tokenizer carries HF's "unset" sentinel,
        # which HFTokenizer reads as the encoder default of 512 and would
        # cut every longer prompt to.
        tokenizer = HFTokenizer(
            config.tokenizer_name or config.pretrained_model_name_or_path,
            model_max_length=config.max_model_len,
            trust_remote_code=config.trust_remote_code,
        )
        # vLLM parity: checkpoints commonly carry EOS (or EXTRA stop ids
        # like gemma-2-it's <end_of_turn>) only in generation_config.json;
        # honoring just the tokenizer's eos would generate to max_tokens.
        gc_eos = _generation_config_eos(config.pretrained_model_name_or_path)
        if getattr(tokenizer._tok, 'eos_token_id', None) is not None:
            tokenizer.eos_id = int(tokenizer._tok.eos_token_id)
        elif gc_eos:
            tokenizer.eos_id = gc_eos[0]
        self._extra_stop_ids = tuple(
            i for i in gc_eos if i != getattr(tokenizer, 'eos_id', None)
        )
        self.engine = LLMEngine(
            model_cfg,
            params,
            tokenizer,
            EngineConfig(
                block_size=config.block_size,
                num_blocks=config.num_blocks,
                max_num_seqs=config.max_num_seqs,
                max_model_len=config.max_model_len,
                quantization=quant_mode,
                # 'auto' is passed THROUGH: the engine resolves it once at
                # construction (where it also knows the mesh and the KV
                # block geometry — a pre-resolved 'pallas' would read as
                # an explicit pin to the engine's TP guard and raise
                # instead of quietly keeping XLA) and logs the fallback.
                attn_backend=config.attn_backend,
                # None = inherit EngineConfig's defaults (single owner).
                **{
                    knob: value
                    for knob, value in (
                        ('decode_steps', config.decode_steps),
                        ('sampling_top_window', config.sampling_top_window),
                        ('enable_prefix_cache', config.enable_prefix_cache),
                        ('prefill_chunk_tokens', config.prefill_chunk_tokens),
                        (
                            'enable_mixed_batching',
                            config.enable_mixed_batching,
                        ),
                        (
                            'max_window_prefill_tokens',
                            config.max_window_prefill_tokens,
                        ),
                        ('draft_k', config.draft_k),
                        ('spec_ngram', config.spec_ngram),
                        ('ttft_slo_s', config.ttft_slo_s),
                        ('request_deadline_s', config.request_deadline_s),
                        (
                            'max_dispatch_retries',
                            config.max_dispatch_retries,
                        ),
                        ('admission_control', config.admission_control),
                    )
                    if value is not None
                },
            ),
            mesh=mesh,
            # The generator created these params itself; let the engine
            # apply destructive HBM optimizations (relayout/quant cleanup).
            own_params=True,
        )

    def _sampling_params(self) -> SamplingParams:
        return SamplingParams(
            temperature=self.config.temperature,
            top_p=self.config.top_p or 1.0,
            min_p=self.config.min_p,
            max_tokens=self.config.max_tokens,
            # generation_config stop ids beyond the primary EOS
            # (gemma-2-it's <end_of_turn>): every entry terminates.
            stop_token_ids=self._extra_stop_ids,
        )

    def generate(self, prompts: str | list[str]) -> list[str]:
        if isinstance(prompts, str):
            prompts = [prompts]
        return self.engine.generate(prompts, self._sampling_params())

    def shutdown(self) -> None:
        self.engine.shutdown()


class FakeGeneratorConfig(BaseConfig):
    """Deterministic local test backend (no reference equivalent — the
    reference relies on downloading small real models; SURVEY.md section 4)."""

    name: Literal['fake'] = 'fake'
    response_template: str = 'response to: {prompt}'
    max_prompt_chars: int = 48
    # Every Nth generate() call raises resilience.EngineOverloaded (the
    # engine's SLO-shed signal) so the chat server's 429/Retry-After
    # surface is testable without a real overloaded engine; 0 disables.
    overload_every: int = 0


class FakeGenerator:
    def __init__(self, config: FakeGeneratorConfig) -> None:
        self.config = config
        self._calls = 0

    def generate(self, prompts: str | list[str]) -> list[str]:
        if isinstance(prompts, str):
            prompts = [prompts]
        self._calls += 1
        every = self.config.overload_every
        if every > 0 and self._calls % every == 0:
            from distllm_tpu.resilience import EngineOverloaded

            raise EngineOverloaded(
                predicted_ttft_s=1.25, retry_after_s=3.0, slo_s=0.5
            )
        return [
            self.config.response_template.format(
                prompt=p[: self.config.max_prompt_chars]
            )
            for p in prompts
        ]
