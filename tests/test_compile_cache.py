"""Persistent XLA compilation cache, placed from outside: with
``JAX_COMPILATION_CACHE_DIR`` set nothing is set in code (JAX reads it);
unset, the cache is the fixed ``.jax_cache/`` of the checkout. One compile per
geometry across Trainer/Engine instances, trials and processes."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
CHECKOUT_CACHE = os.path.join(REPO, ".jax_cache")

SCRIPT = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, {repo!r})
    import jax
    from maggy_tpu import util

    # what JAX itself read from the environment, before any of our code ran
    from_env = jax.config.jax_compilation_cache_dir
    d = util.enable_compilation_cache()
    assert util.enable_compilation_cache() == d  # idempotent
    assert jax.config.jax_compilation_cache_dir == (d or from_env)
    if d is not None and os.environ.get("WARM"):
        import jax.numpy as jnp
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones((8, 8))).block_until_ready()
    print("CACHE", d, "ENV", from_env)
    """
).format(repo=REPO)


def _run(tmp_path, **env_overrides):
    script = tmp_path / "cache_probe.py"
    script.write_text(SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in ("MAGGY_TPU_COMPILE_CACHE", "JAX_COMPILATION_CACHE_DIR", "WARM"):
        env.pop(name, None)
    env.update(env_overrides)
    proc = subprocess.run(
        [sys.executable, str(script)], env=env, capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_env_dir_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the function reports JAX's directory,
    sets none of its own, and compiles fill that directory, not the
    checkout's."""
    outside = tmp_path / "outside"
    before = set(os.listdir(CHECKOUT_CACHE)) if os.path.isdir(CHECKOUT_CACHE) else None
    out = _run(
        tmp_path, MAGGY_TPU_COMPILE_CACHE="1", WARM="1",
        JAX_COMPILATION_CACHE_DIR=str(outside),
    )
    assert out == f"CACHE {outside} ENV {outside}"
    assert os.listdir(outside)
    after = set(os.listdir(CHECKOUT_CACHE)) if os.path.isdir(CHECKOUT_CACHE) else None
    assert after == before


def test_unset_uses_the_fixed_checkout_path(tmp_path):
    out = _run(tmp_path, MAGGY_TPU_COMPILE_CACHE="1")
    assert out == f"CACHE {CHECKOUT_CACHE} ENV None"
    assert os.path.isdir(CHECKOUT_CACHE)


@pytest.mark.parametrize("flag", [None, "0"])
def test_cache_stays_off_on_cpu_unless_forced(monkeypatch, flag):
    """Off by default on a CPU backend (XLA:CPU AOT reloads can SIGILL across
    machine-feature drift) and when disabled explicitly; nothing is set, so
    this runs in-process."""
    import jax

    from maggy_tpu import util

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if flag is None:
        monkeypatch.delenv("MAGGY_TPU_COMPILE_CACHE", raising=False)
    else:
        monkeypatch.setenv("MAGGY_TPU_COMPILE_CACHE", flag)
    before = jax.config.jax_compilation_cache_dir
    assert util.enable_compilation_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
