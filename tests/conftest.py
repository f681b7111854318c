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


@pytest.fixture
def joins_a_retired_row():
    """The served models' engine test of an admission that joins the
    steps in flight (see :func:`_joins_a_retired_row`)."""
    return _joins_a_retired_row


def _joins_a_retired_row(model, params, pool, eng):
    """Two rows and two state slots. B decodes; A is cancelled after
    three tokens, and C, submitted once A's stream has closed, is
    prefilled behind the steps that still name A: it takes A's state
    slot and blocks and, in the next step, A's row (the one hole of a
    two-row bucket) with no landing; those steps write A's row before
    C's commit. Every stream is the reference's greedy tokens. Returns
    C's ``generate.prefill`` args."""
    import numpy as np

    from deeplearning4j_tpu.common import telemetry
    rs = np.random.RandomState(11)
    pa, pb, pc = (rs.randint(2, 90, n) for n in (7, 5, 9))
    n_events = len(telemetry.trace_events())
    b = eng.submit(pb, 24)
    a = eng.submit(pa, 40)
    head = [a.next(timeout=120) for _ in range(3)]
    slot, blocks = pool.slot(a.seq_id), set(pool.table(a.seq_id))
    a.cancel()
    head += list(a)                 # the stream closes as A retires
    c = eng.submit(pc, 6)
    assert set(pool.table(c.seq_id)) & blocks
    got_c, got_b = list(c), list(b)
    eng.shutdown()
    assert a.reason == "cancelled"
    assert c.reason == b.reason == "max_tokens"
    for prompt, got in ((pa, head), (pb, got_b), (pc, got_c)):
        # greedy: every served token is what the reference's forward
        # over the prompt and the served tokens puts first there
        logits = np.asarray(model.forward(
            params, np.asarray([list(prompt) + got], np.int32))[0])
        at = np.arange(len(prompt) - 1, len(prompt) + len(got) - 1)
        assert got == logits[at].argmax(-1).tolist()
    assert len(got_b) == 24 and len(got_c) == 6
    assert eng.retraces_since_warmup() == 0
    assert pool.free_slots == 2 and pool.free_blocks == pool.usable_blocks
    events = [e for e in telemetry.trace_events()[n_events:]
              if e.get("ph") == "X"]
    (prefill,) = [e["args"] for e in events if e["name"]
                  == "generate.prefill" and e["args"]["seq"] == c.seq_id]
    assert prefill["state_slot"] == slot
    i = prefill["iter"]
    (admit,) = [e["args"] for e in events if e["name"] == "generate.admit"
                and e["args"]["iter"] == i]
    assert admit["admitted"] == admit["behind"] == admit["joined"] == 1
    # the admitting pass builds no step: the next one takes C in
    (step,) = [e["args"] for e in events if e["name"]
               == "generate.decode_step" and e["args"]["iter"] == i + 1]
    assert step["live"] == step["bucket"] == 2
    return prefill
