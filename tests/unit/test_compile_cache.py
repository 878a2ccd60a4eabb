"""Persistent XLA compilation cache wiring (unionml_tpu/compile_cache.py):
``JAX_COMPILATION_CACHE_DIR`` places the cache from outside and nothing in the
package overrides it; unset, the default is one fixed path inside the checkout."""

import os
import subprocess
import sys

import jax
import pytest

from unionml_tpu import compile_cache, enable_compile_cache
from unionml_tpu.compile_cache import _maybe_enable_from_env

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def restore_jax_cache_config(monkeypatch):
    """These tests mutate process-global JAX config; later tests in the same
    pytest process must not inherit a cache dir pointing at a deleted tmpdir.
    The outside placement is cleared so each test states its own environment."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("UNIONML_TPU_COMPILE_CACHE", raising=False)
    cache_dir = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)


def test_enable_sets_jax_config_and_creates_dir(tmp_path):
    target = tmp_path / "xla-cache"
    resolved = enable_compile_cache(str(target))
    assert resolved == str(target)
    assert target.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(target)


@pytest.mark.parametrize("source", ["package_env", "argument"])
def test_flag_uses_default_location(monkeypatch, source):
    # "1" means "on, default location" (UNIONML_TPU_COMPILE_CACHE=1, --compile-cache 1):
    # the checkout's own .xla_cache, resolved from the package's location — never
    # $HOME, a tempfile, a pid, a timestamp, or a directory called "1"
    monkeypatch.setenv("HOME", "/nonexistent-home")
    if source == "package_env":
        monkeypatch.setenv("UNIONML_TPU_COMPILE_CACHE", "1")
    resolved = enable_compile_cache("1" if source == "argument" else None)
    assert resolved == os.path.join(REPO, ".xla_cache")
    assert os.path.isdir(resolved)


def test_env_path_wins_and_import_hook_applies_it(tmp_path, monkeypatch):
    target = tmp_path / "from-env"
    monkeypatch.setenv("UNIONML_TPU_COMPILE_CACHE", str(target))
    _maybe_enable_from_env()
    assert jax.config.jax_compilation_cache_dir == str(target)
    assert target.is_dir()


def test_import_hook_respects_off_flags(monkeypatch):
    # inherited-env opt-out: a child of the benchmark suite can disable the
    # cache with =0 without the value being mistaken for a directory path
    for off in ("0", "false", "no", "off"):
        monkeypatch.setenv("UNIONML_TPU_COMPILE_CACHE", off)
        before = jax.config.jax_compilation_cache_dir
        _maybe_enable_from_env()
        assert jax.config.jax_compilation_cache_dir == before
        assert not os.path.exists(off)


def test_jitted_program_lands_in_the_cache(tmp_path):
    """End-to-end: compiling under the cache writes an entry (CPU backend
    serializes executables, so this exercises the real write path)."""
    import jax.numpy as jnp

    target = tmp_path / "cache-e2e"
    enable_compile_cache(str(target))
    # force caching of even sub-second compiles for the test
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    f(jnp.ones((64, 64))).block_until_ready()
    entries = list(target.iterdir())
    assert entries, "no cache entry written"


@pytest.mark.parametrize("how", ["argument", "package_env", "import_hook"])
def test_jax_env_dir_is_never_overridden_in_code(tmp_path, monkeypatch, how):
    """With JAX_COMPILATION_CACHE_DIR set JAX owns the directory: the package
    reports it and never points the config anywhere, whatever its own knobs say."""
    outside, other = tmp_path / "outside", tmp_path / "other"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(outside))
    updates = []
    with monkeypatch.context() as patched:  # undone before the autouse fixture restores the config
        patched.setattr(jax.config, "update", lambda name, value: updates.append((name, value)))
        if how == "argument":
            resolved = enable_compile_cache(str(other))
        else:
            monkeypatch.setenv("UNIONML_TPU_COMPILE_CACHE", str(other))
            resolved = enable_compile_cache() if how == "package_env" else _maybe_enable_from_env()
    assert resolved in (str(outside), None)  # the import hook returns nothing
    assert not [u for u in updates if u[0] == "jax_compilation_cache_dir"]
    assert outside.is_dir() and not other.exists()


_CHILD = """
import jax, jax.numpy as jnp, unionml_tpu
from unionml_tpu.compile_cache import _DEFAULT_DIR
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: (x @ x.T).sum())(jnp.ones((32, 32))).block_until_ready()
print(_DEFAULT_DIR)
print(jax.config.jax_compilation_cache_dir)
"""


def test_default_is_one_path_across_processes_and_env_dir_holds_the_entries(tmp_path):
    """Two fresh interpreters resolve the same default (a moving directory never
    hits); one of them, placed from outside, writes there and nowhere else."""
    outside, other = tmp_path / "outside", tmp_path / "other"
    base = {k: v for k, v in os.environ.items() if k not in ("JAX_COMPILATION_CACHE_DIR", "UNIONML_TPU_COMPILE_CACHE")}
    base["PYTHONPATH"] = REPO
    placed = dict(base, JAX_COMPILATION_CACHE_DIR=str(outside), UNIONML_TPU_COMPILE_CACHE=str(other))
    outs = [
        subprocess.run(
            [sys.executable, "-c", _CHILD], env=env, cwd=str(tmp_path), capture_output=True, text=True, timeout=120
        )
        for env in (dict(base, UNIONML_TPU_COMPILE_CACHE="0"), placed)
    ]
    assert all(o.returncode == 0 for o in outs), [o.stderr[-2000:] for o in outs]
    (default_a, configured_a), (default_b, configured_b) = (o.stdout.split() for o in outs)
    assert default_a == default_b == compile_cache._DEFAULT_DIR == os.path.join(REPO, ".xla_cache")
    assert configured_a == "None"  # opt-in stays opt-in: a bare import enables nothing
    assert configured_b == str(outside)
    assert list(outside.iterdir()) and not other.exists()
