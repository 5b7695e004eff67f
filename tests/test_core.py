"""Core layer tests: config IO, batching, timers, warmstart registry."""

from pathlib import Path
from typing import Literal

import pytest

from distllm_tpu import __version__
from distllm_tpu.registry import WarmstartRegistry, register, registry
from distllm_tpu.timer import TimeLogger, Timer
from distllm_tpu.utils import BaseConfig, batch_data, expo_backoff_retry


def test_version():
    assert __version__


class _DemoSub(BaseConfig):
    name: Literal['demo'] = 'demo'
    width: int = 4


class _DemoConfig(BaseConfig):
    title: str
    sub: _DemoSub = _DemoSub()


def test_config_yaml_roundtrip(tmp_path):
    cfg = _DemoConfig(title='hello', sub=_DemoSub(width=7))
    path = tmp_path / 'cfg.yaml'
    cfg.write_yaml(path)
    loaded = _DemoConfig.from_yaml(path)
    assert loaded == cfg


def test_config_json_roundtrip(tmp_path):
    cfg = _DemoConfig(title='x')
    path = tmp_path / 'cfg.json'
    cfg.write_json(path)
    assert _DemoConfig.from_json(path) == cfg


def test_config_env_substitution(tmp_path, monkeypatch):
    monkeypatch.setenv('DISTLLM_TEST_TITLE', 'from-env')
    path = tmp_path / 'cfg.yaml'
    path.write_text('title: ${env:DISTLLM_TEST_TITLE}\n')
    assert _DemoConfig.from_yaml(path).title == 'from-env'


def test_config_rejects_unknown_fields():
    with pytest.raises(Exception):
        _DemoConfig(title='x', bogus=1)


def test_batch_data():
    assert batch_data([1, 2, 3, 4, 5], 2) == [[1, 2], [3, 4], [5]]
    assert batch_data([], 3) == []
    assert batch_data([1], 10) == [[1]]
    with pytest.raises(ValueError):
        batch_data([1], 0)


def test_timer_roundtrip(capsys):
    with Timer('stage-a', 'file-1'):
        pass
    with Timer('stage-a', 'file-2'):
        pass
    with Timer('stage-b'):
        pass
    out = capsys.readouterr().out
    stats = TimeLogger().parse_lines(out)
    assert stats[('stage-a', 'file-1')].count == 1
    assert stats[('stage-b',)].count == 1
    assert stats[('stage-b',)].total_s >= 0


def test_timer_logfile(tmp_path, capsys):
    with Timer('x'):
        pass
    log = tmp_path / 'log.txt'
    log.write_text(capsys.readouterr().out)
    stats = TimeLogger().parse_logs(log)
    assert ('x',) in stats


class _Expensive:
    built = 0

    def __init__(self, size):
        self.size = size
        _Expensive.built += 1
        self.dead = False

    def shutdown(self):
        self.dead = True


def test_registry_warmstart():
    reg = WarmstartRegistry()
    a = reg.get(_Expensive, size=1)
    b = reg.get(_Expensive, size=1)
    assert a is b  # cache hit, no rebuild
    c = reg.get(_Expensive, size=2)
    assert c is not a
    assert a.dead  # old instance shut down on swap


def test_registry_slots():
    reg = WarmstartRegistry(max_slots=2)
    a = reg.get(_Expensive, slot='encoder', size=1)
    g = reg.get(_Expensive, slot='generator', size=9)
    assert reg.get(_Expensive, slot='encoder', size=1) is a
    assert reg.get(_Expensive, slot='generator', size=9) is g


def test_register_decorator():
    calls = []

    @register(slot='test-deco')
    def make(value: int):
        calls.append(value)
        return {'value': value}

    r1 = make(value=5)
    r2 = make(value=5)
    assert r1 is r2
    assert calls == [5]
    registry().clear()


def test_expo_backoff_retry():
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise RuntimeError('boom')
        return 'ok'

    assert expo_backoff_retry(flaky, sleep=lambda _: None) == 'ok'
    assert len(attempts) == 3

    class AuthError(Exception):
        pass

    def fatal():
        raise AuthError('no')

    with pytest.raises(AuthError):
        expo_backoff_retry(
            fatal, give_up_on=(AuthError,), sleep=lambda _: None
        )


class TestInstantiate:
    """``_target_`` class dispatch (reference ``chat_argoproxy.py:511-549``)."""

    def test_target_dispatch(self):
        from distllm_tpu.utils import instantiate

        obj = instantiate(
            {'_target_': 'pathlib.PurePosixPath', 'args': None}
            | {'_target_': 'collections.Counter'},
            _allow_=('collections.',),
        )
        import collections

        assert isinstance(obj, collections.Counter)

    def test_target_outside_allowlist_rejected(self):
        # Unrestricted import+call would let any loaded YAML execute
        # arbitrary code; default allowlist is distllm_tpu.* only.
        from distllm_tpu.utils import instantiate

        with pytest.raises(ValueError, match='allowed prefixes'):
            instantiate({'_target_': 'os.system', 'command': 'true'})

    def test_target_within_package_allowed_by_default(self):
        from distllm_tpu.utils import instantiate

        timer = instantiate({'_target_': 'distllm_tpu.timer.Timer'})
        from distllm_tpu.timer import Timer

        assert isinstance(timer, Timer)

    def test_nested_and_env(self, monkeypatch):
        from distllm_tpu.utils import instantiate

        monkeypatch.setenv('VFY_NAME', 'hello')
        out = instantiate(
            {
                'inner': {'_target_': 'fractions.Fraction', 'numerator': 3},
                'plain': '${env:VFY_NAME}',
            },
            _allow_=('fractions.',),
        )
        import fractions

        assert out['inner'] == fractions.Fraction(3)
        assert out['plain'] == 'hello'

    def test_bad_target_raises(self):
        from distllm_tpu.utils import instantiate

        with pytest.raises(ValueError, match='dotted path'):
            instantiate({'_target_': 'NoDots'})

    def test_passthrough(self):
        from distllm_tpu.utils import instantiate

        assert instantiate({'a': [1, 2]}) == {'a': [1, 2]}


@pytest.mark.parametrize('env_set', [True, False], ids=['env_set', 'env_unset'])
def test_enable_compile_cache_places_the_cache(monkeypatch, tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code (jax reads the
    variable itself). Unset: ``<checkout>/.jax_cache``, a fixed path."""
    import jax

    from distllm_tpu import utils

    before = jax.config.jax_compilation_cache_dir
    sentinel = str(tmp_path / 'untouched')
    try:
        jax.config.update('jax_compilation_cache_dir', sentinel)
        if env_set:
            monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
            assert utils.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == sentinel
        else:
            monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
            want = str(Path(utils.__file__).resolve().parents[1] / '.jax_cache')
            assert utils.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update('jax_compilation_cache_dir', before)


def test_canonical_function_rebinds_main():
    from distllm_tpu.utils import batch_data, canonical_function

    # Functions already owned by an importable module pass through.
    assert canonical_function(batch_data, 'distllm_tpu.utils') is batch_data

    # A __main__-defined function (driver run via `python -m`) is re-resolved
    # from its canonical module so fabric workers can unpickle it.
    import types

    fake_main = types.FunctionType(
        batch_data.__code__, batch_data.__globals__, 'batch_data'
    )
    fake_main.__module__ = '__main__'
    assert (
        canonical_function(fake_main, 'distllm_tpu.utils') is batch_data
    )
