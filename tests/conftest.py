"""Test harness configuration.

Runs everything on CPU with 8 virtual XLA devices so multi-chip sharding logic is
exercised without TPU hardware (SURVEY.md §4 "in-process fake cluster"). Must be
set before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest


@pytest.fixture()
def tmp_env(tmp_path):
    """Point the ambient Env at a per-test temp dir."""
    from maggy_tpu.core import env as env_mod
    from maggy_tpu.core.env.base import BaseEnv

    old_root = os.environ.get("MAGGY_TPU_LOG_ROOT")
    os.environ["MAGGY_TPU_LOG_ROOT"] = str(tmp_path)
    env_mod.set_instance(BaseEnv(str(tmp_path)))
    yield env_mod.get_instance()
    env_mod.set_instance(None)
    if old_root is None:
        os.environ.pop("MAGGY_TPU_LOG_ROOT", None)
    else:
        os.environ["MAGGY_TPU_LOG_ROOT"] = old_root
