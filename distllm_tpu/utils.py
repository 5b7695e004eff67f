"""Core configuration and small utilities.

Behavioral parity target: ``distllm/utils.py:20-128`` in the reference —
pydantic config models with YAML/JSON round-trip, list batching, and a
download helper. The implementation is original; configs additionally support
environment-variable substitution (``${env:VAR}``) which the reference only
offers in its chat app (``chat_argoproxy.py:511-549``).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
from pathlib import Path
from typing import Any, Callable, Iterator, TypeVar

import yaml
from pydantic import BaseModel, ConfigDict

T = TypeVar('T')

PathLike = str | Path

_ENV_PATTERN = re.compile(r'\$\{env:([A-Za-z_][A-Za-z0-9_]*)\}')


def _substitute_env(obj: Any) -> Any:
    """Recursively replace ``${env:VAR}`` markers in strings with os.environ."""
    if isinstance(obj, str):
        return _ENV_PATTERN.sub(lambda m: os.environ.get(m.group(1), ''), obj)
    if isinstance(obj, dict):
        return {k: _substitute_env(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_substitute_env(v) for v in obj]
    return obj


class BaseConfig(BaseModel):
    """Pydantic base for every config object in the framework.

    Subclasses declare a ``name: Literal['...']`` tag where they participate in
    a discriminated union dispatched by a strategy factory (the same
    YAML-driven composition scheme the reference uses throughout).
    """

    model_config = ConfigDict(extra='forbid', validate_assignment=True)

    @classmethod
    def from_yaml(cls: type[T], path: PathLike) -> T:
        with open(path) as fh:
            raw = yaml.safe_load(fh) or {}
        return cls(**_substitute_env(raw))

    @classmethod
    def from_json(cls: type[T], path: PathLike) -> T:
        with open(path) as fh:
            raw = json.load(fh)
        return cls(**_substitute_env(raw))

    def write_yaml(self, path: PathLike) -> None:
        Path(path).parent.mkdir(parents=True, exist_ok=True)
        with open(path, 'w') as fh:
            yaml.safe_dump(
                json.loads(self.model_dump_json()), fh, sort_keys=False
            )

    def write_json(self, path: PathLike) -> None:
        Path(path).parent.mkdir(parents=True, exist_ok=True)
        with open(path, 'w') as fh:
            fh.write(self.model_dump_json(indent=2))


#: Import prefixes ``instantiate`` accepts by default. The reference
#: dispatches ``_target_`` through an explicit class allowlist
#: (``chat_argoproxy.py:511-549``); an unrestricted import+call would let
#: any loaded YAML execute arbitrary code. Extend via the ``allow``
#: argument for operator-trusted configs.
INSTANTIATE_ALLOWED_PREFIXES: tuple[str, ...] = ('distllm_tpu.',)


def instantiate(
    config: Any, _allow_: tuple[str, ...] | None = None, **overrides: Any
) -> Any:
    """``_target_``-field class dispatch (reference ``chat_argoproxy.py:511-549``).

    A dict carrying ``_target_: 'pkg.module.ClassName'`` is resolved by
    import and constructed from the remaining keys; nested dicts instantiate
    recursively (depth-first), and ``${env:VAR}`` markers substitute first.
    Non-``_target_`` values pass through unchanged. Targets must fall under
    ``INSTANTIATE_ALLOWED_PREFIXES`` (or the explicit ``_allow_`` prefixes —
    underscored like ``_target_`` so it can never collide with a
    constructor override name).
    """
    config = _substitute_env(config)
    allowed = INSTANTIATE_ALLOWED_PREFIXES + tuple(_allow_ or ())

    def build(obj: Any) -> Any:
        if isinstance(obj, dict):
            built = {k: build(v) for k, v in obj.items() if k != '_target_'}
            target = obj.get('_target_')
            if target is None:
                return built
            import importlib

            module_name, _, attr = str(target).rpartition('.')
            if not module_name:
                raise ValueError(
                    f"_target_ must be a dotted path, got {target!r}"
                )
            if not any(str(target).startswith(p) for p in allowed):
                raise ValueError(
                    f"_target_ {target!r} is outside the allowed prefixes "
                    f'{allowed}; pass allow=("your.pkg.",) for '
                    'operator-trusted configs'
                )
            cls = getattr(importlib.import_module(module_name), attr)
            return cls(**built)
        if isinstance(obj, list):
            return [build(v) for v in obj]
        return obj

    if isinstance(config, dict):
        config = {**config, **overrides}
    return build(config)


def enable_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, so nothing is
    set in code. Unset: ``<checkout>/.jax_cache`` — a fixed path, because
    the path is part of the cache key and a directory that moves never
    hits. Call first thing in every entry point, before the first compile:
    the process's compile watcher listens from here on, so what an entry
    point compiles before it builds an engine (its weights) is on the
    start-up account too (``observability/startup.py``).
    """
    from distllm_tpu.observability.startup import get_compile_watcher

    get_compile_watcher().listen()
    env_dir = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env_dir:
        return env_dir
    import jax

    cache_dir = str(Path(__file__).resolve().parents[1] / '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', cache_dir)
    return cache_dir


def batch_data(data: list[T], batch_size: int) -> list[list[T]]:
    """Split ``data`` into consecutive chunks of at most ``batch_size``.

    Parity with ``distllm/utils.py:91-112``; every element appears exactly
    once and order is preserved.
    """
    if batch_size < 1:
        raise ValueError(f'batch_size must be >= 1, got {batch_size}')
    return [data[i : i + batch_size] for i in range(0, len(data), batch_size)]


def iter_batches(data: list[T], batch_size: int) -> Iterator[list[T]]:
    """Lazy variant of :func:`batch_data` for large corpora."""
    if batch_size < 1:
        raise ValueError(f'batch_size must be >= 1, got {batch_size}')
    for i in range(0, len(data), batch_size):
        yield data[i : i + batch_size]


def curl_download(url: str, output_path: PathLike, timeout: int = 600) -> Path:
    """Download ``url`` to ``output_path`` via curl if not already present.

    Parity with ``distllm/utils.py:115-128`` (used by the QA eval tasks to
    fetch datasets). Skips the download when the file already exists.
    """
    output_path = Path(output_path)
    if output_path.exists():
        return output_path
    output_path.parent.mkdir(parents=True, exist_ok=True)
    # Download to a temp name and rename on success so a failed transfer
    # never leaves a partial file that later calls mistake for a cache hit.
    tmp_path = output_path.with_name(output_path.name + '.part')
    subprocess.run(
        ['curl', '-fsSL', url, '-o', str(tmp_path)],
        check=True,
        timeout=timeout,
    )
    tmp_path.rename(output_path)
    return output_path


def canonical_function(fn: Callable, module: str) -> Callable:
    """Re-resolve ``fn`` from its importable module when it was defined in
    ``__main__`` (a driver run as ``python -m ...``). Pickle serializes
    functions by module path, and ``__main__`` inside a fabric worker is
    ``distllm_tpu.parallel.worker`` — the worker could never resolve the
    driver's function without this."""
    if getattr(fn, '__module__', None) != '__main__':
        return fn
    import importlib

    return getattr(importlib.import_module(module), fn.__name__)


def expo_backoff_retry(
    fn,
    *,
    max_tries: int = 5,
    base_delay: float = 1.0,
    max_delay: float = 30.0,
    retry_on: tuple[type[BaseException], ...] = (Exception,),
    give_up_on: tuple[type[BaseException], ...] = (),
    jitter: bool = True,
    sleep=None,
):
    """Call ``fn()`` with exponential backoff (own impl; ``backoff`` pkg absent).

    Parity target: ``@backoff.expo`` usage in the reference MCQA harness
    (``mcqa/rag_argonium_score_parallel_v3.py:1957-1963``) — expo delays with
    jitter, a bounded number of tries, and give-up exception types (the
    reference gives up on auth errors).
    """
    import random
    import time

    if sleep is None:
        sleep = time.sleep
    last: BaseException | None = None
    for attempt in range(max_tries):
        try:
            return fn()
        except give_up_on:
            raise
        except retry_on as exc:  # noqa: PERF203
            last = exc
            if attempt == max_tries - 1:
                raise
            delay = min(max_delay, base_delay * (2**attempt))
            if jitter:
                delay *= 0.5 + random.random() / 2
            sleep(delay)
    raise last  # pragma: no cover - unreachable
