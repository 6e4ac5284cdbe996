"""common/compile_cache.py: one rule for where compiled programs go."""
import os

import jax

from determined_tpu.common import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: calls.append((key, value))
    )
    return calls


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR is jax's own variable: when whoever runs
    the machine set it, the helper reports it and touches no config —
    not even for an experiment's override."""
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv(compile_cache.ENV, "/some/dir")
    assert compile_cache.enable() == "/some/dir"
    assert compile_cache.enable("/from/expconf") == "/some/dir"
    assert calls == []


def test_default_is_a_fixed_path_inside_the_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    want = os.path.join(REPO, ".cache", "xla")
    assert compile_cache.enable() == want
    assert compile_cache.cache_dir() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    # fixed: no pid, no timestamp, no /tmp — the path is part of the key
    assert compile_cache.enable() == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".cache/" in f.read().split()


def test_experiment_override_sits_below_the_env_var(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.enable("/from/expconf") == "/from/expconf"
    assert calls == [("jax_compilation_cache_dir", "/from/expconf")]


def test_entry_count(tmp_path):
    assert compile_cache.entry_count(str(tmp_path / "absent")) == 0
    (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
    assert compile_cache.entry_count(str(tmp_path)) == 1
