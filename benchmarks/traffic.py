"""The one general traffic generator. A traffic mix is a data file of
parameters (``workloads/<cell>.json`` ``traffic``); this module turns the
parameters and ``--seed`` into inputs. The program under test receives only
the generated inputs.

Every seed gets the SAME set of sizes and of gaps between arrivals: the
evenly spaced quantiles of the stated distribution, not draws. For requests
their order too is the cell's (``schedule_seed`` in its file), not the run's:
which long prompt meets which burst decides how far a KV pool near its limit
is pushed, so a seed that reordered them would change the work. ``--seed``
makes the content: token ids, texts, and (in the drivers) weights.

Adapted from ``distllm_tpu/generate/loadgen.py build_workload`` (seeded
Poisson arrivals). Two repairs: lengths are log-uniform where a tail is
wanted (the original's are uniform), and sizes are quantiles, not draws.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of one seed (any whole number)."""
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFFFFFFFFFF, *stream.encode()]
    )


def sizes(spec: dict, count: int, rng: np.random.Generator) -> list[int]:
    """``count`` whole sizes from ``{"dist", "lo", "hi"}`` (both ends
    inclusive), or ``{"dist": "fixed", "value"}``: the distribution's evenly
    spaced quantiles, in an order the seed chooses."""
    dist = spec['dist']
    if dist == 'fixed':
        return [int(spec['value'])] * count
    lo, hi = int(spec['lo']), int(spec['hi'])
    if not 0 < lo <= hi:
        raise ValueError(f'bad size range {spec}')
    u = (np.arange(count) + 0.5) / count
    if dist == 'uniform':
        values = lo + u * (hi + 1 - lo)
    elif dist == 'loguniform':
        values = np.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo)))
    else:
        raise ValueError(f'unknown size distribution {dist!r}')
    out = np.clip(np.floor(values).astype(int), lo, hi)
    rng.shuffle(out)
    return [int(v) for v in out]


def poisson_arrivals(
    rate_rps: float, seconds: float, rng: np.random.Generator
) -> list[float]:
    """Arrival offsets of a Poisson process of ``rate_rps`` over ``seconds``:
    ``round(rate * seconds)`` arrivals whose gaps are the evenly spaced
    quantiles of the exponential distribution, shuffled by the seed and
    scaled so that the last arrival falls just inside the window."""
    count = max(1, round(rate_rps * seconds))
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u) / rate_rps
    rng.shuffle(gaps)
    at = np.cumsum(gaps)
    at *= (seconds * count / (count + 1)) / at[-1]
    return [float(t) for t in at]


def token_ids(length: int, vocab_size: int, rng: np.random.Generator) -> list[int]:
    """``length`` token ids in ``[4, vocab_size)`` (low ids are specials)."""
    return [int(t) for t in rng.integers(4, vocab_size, size=length)]


def corpus(spec: dict, seed: int) -> list[str]:
    """A corpus of chunk-sized texts: ``count`` texts of ``words`` (a size
    spec) words each, drawn from ``vocab_words`` distinct words."""
    rng = rng_for(seed, 'corpus')
    lengths = sizes(spec['words'], int(spec['count']), rng)
    vocab = np.array([f'w{i}' for i in range(int(spec['vocab_words']))])
    return [' '.join(rng.choice(vocab, size=n)) for n in lengths]


@dataclass(frozen=True)
class Request:
    at_s: float  # offset from the window's start; 0.0 in a closed loop
    prompt_ids: tuple[int, ...]
    max_tokens: int


def schedule_rng(spec: dict, stream: str) -> np.random.Generator:
    """The generator that orders a cell's sizes and gaps: the cell's own."""
    return rng_for(int(spec.get('schedule_seed', 0)), f'schedule/{stream}')


def requests(
    spec: dict, count: int, vocab_size: int, seed: int, stream: str,
    arrivals: list[float] | None = None, order_stream: str | None = None,
) -> list[Request]:
    """``count`` requests with ``prompt_tokens`` and ``output_tokens`` size
    specs, ordered by the cell's ``schedule_seed``; token ids from ``seed``.
    ``order_stream`` names the stream that orders the sizes where it is not
    ``stream``: calls that give the same one get the same sizes in the same
    order, with other token ids.
    ``shared_prefix`` (``{"sessions", "tokens"}``, optional) makes each
    request start with one of ``sessions`` fixed prefixes."""
    order = schedule_rng(spec, order_stream or stream)
    prompt_lens = sizes(spec['prompt_tokens'], count, order)
    output_lens = sizes(spec['output_tokens'], count, order)
    rng = rng_for(seed, stream)
    shared = spec.get('shared_prefix') or {}
    prefixes = [
        token_ids(int(shared['tokens']), vocab_size, rng)
        for _ in range(int(shared.get('sessions', 0)))
    ]
    out = []
    for i in range(count):
        body = token_ids(prompt_lens[i], vocab_size, rng)
        if prefixes:
            body = prefixes[int(rng.integers(len(prefixes)))] + body
        out.append(
            Request(
                at_s=arrivals[i] if arrivals is not None else 0.0,
                prompt_ids=tuple(body),
                max_tokens=output_lens[i],
            )
        )
    return out
