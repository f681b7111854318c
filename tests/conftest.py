"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (SURVEY.md section 4.7 TPU
translation: multi-worker semantics in one process) so the full suite —
including sharding/collective tests — runs without TPU hardware. A run on
the real accelerator is forced with DL4J_TPU_TEST_PLATFORM=tpu (on the
machine that has one).

Backend *initialization* is lazy, so setting jax_platforms + XLA_FLAGS
here (before any jax.devices() call) takes effect. Do not call
jax.devices() at import time in any test module.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

if os.environ.get("DL4J_TPU_TEST_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

#: modules whose every test builds a multi-device mesh — on hardware
#: with fewer devices (e.g. a single chip) they SKIP, not
#: fail: multi-device semantics are validated on the virtual CPU mesh
#: (SURVEY.md section 4.7), the same way the reference validates
#: Spark/parameter-server behavior in local/dummy-transport mode
_MESH_ONLY_MODULES = {
    "test_parallel", "test_tensor_parallel", "test_pipeline_parallel",
    "test_pipeline", "test_expert_parallel", "test_transformer_5d",
    "test_update_sharding", "test_fsdp", "test_elastic",
    "test_2d_parallel", "test_serving_sharded", "test_encoded",
}


def pytest_collection_modifyitems(config, items):
    have = len(jax.devices())
    if have >= 8:
        return
    skip = pytest.mark.skip(
        reason=f"multi-device suite needs the 8-device virtual mesh "
               f"(have {have} device(s); run without "
               f"DL4J_TPU_TEST_PLATFORM=tpu)")
    for item in items:
        mod = item.module.__name__ if item.module else ""
        if mod in _MESH_ONLY_MODULES:
            item.add_marker(skip)


def require_devices(n: int):
    """Per-test guard for MIXED modules (some tests single-device,
    some mesh-based): skip when the platform has fewer devices."""
    have = len(jax.devices())
    if have < n:
        pytest.skip(f"needs {n} devices, have {have}")
