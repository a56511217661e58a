"""Process-level set-up of the entry points: the persistent compilation
cache, CPU pinning of host-only tools, and chip_smoke.py's device guard."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env_extra, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != runtime.CACHE_ENV}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"), **env_extra)
    args = code_or_args if isinstance(code_or_args, list) else \
        ["-c", code_or_args]
    return subprocess.run([sys.executable, *args], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)


def test_checkout_root_is_the_repository():
    assert runtime.checkout_root() == REPO


def test_compile_cache_written_only_where_the_env_says(tmp_path):
    cache = tmp_path / "cache"
    r = _run("import jax, jax.numpy as jnp\n"
             "jax.config.update("
             "'jax_persistent_cache_min_compile_time_secs', 0)\n"
             "from repro.launch.runtime import enable_compile_cache\n"
             "print(enable_compile_cache())\n"
             "jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()",
             {runtime.CACHE_ENV: str(cache)}, tmp_path)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(cache)]
    assert any(cache.iterdir())
    # nothing else was created: not the checkout's default cache either
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache"]
    assert jax.config.jax_compilation_cache_dir != str(cache)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = runtime.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_pin_cpu_keeps_cpu_and_refuses_other_platforms(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    runtime.pin_cpu("tool")                  # this process is on the CPU
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="platform 'tpu'"):
        runtime.pin_cpu("tool")


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    r = _run([os.path.join(REPO, "chip_smoke.py")], {}, tmp_path)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
