"""What the entry points set up: where the persistent compile cache goes,
and how the benchmark runner treats a suite that fails to import."""

import sys

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_defaults_to_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "src" / "repro" / "compile_cache.py").is_file()
    assert jax.config.jax_compilation_cache_dir == path


def test_cache_env_var_wins(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing else is set here
    assert jax.config.jax_compilation_cache_dir is None


def test_benchmark_suite_import_failure_fails_the_run(monkeypatch, capsys):
    from benchmarks import run
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(run, "SUITES", ("no_such_suite",))
    monkeypatch.setattr(sys, "argv", ["run"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code == 1
    assert "no_such_suite,NaN,IMPORT ERROR" in capsys.readouterr().err
