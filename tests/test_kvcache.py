"""Paged KV-cache pool: allocator lifecycle, exhaustion shedding,
block-table chaining, and the memory-report resident class.

The allocator tests run with ``device_arrays=False`` (pure numpy
bookkeeping, no XLA involvement) — block accounting is host logic and
should be testable at host speed. The end-to-end 429 + Retry-After
behavior rides the real server in test_generative.py.
"""
from __future__ import annotations

import numpy as np
import pytest

from deeplearning4j_tpu.common import diagnostics, telemetry
from deeplearning4j_tpu.serving.kvcache import (KVBlockPool,
                                                PoolExhausted,
                                                pool_report,
                                                pool_resident_bytes)


def _pool(num_blocks=8, block=4, **kw):
    kw.setdefault("device_arrays", False)
    return KVBlockPool(2, num_blocks, block, 2, 8, name="t", **kw)


class TestAllocator:
    def test_alloc_rounds_tokens_up_to_blocks(self):
        p = _pool()
        assert p.blocks_for(1) == 1
        assert p.blocks_for(4) == 1
        assert p.blocks_for(5) == 2
        p.alloc("a", 5)
        assert p.live_blocks == 2
        assert len(p.table("a")) == 2
        assert p.length("a") == 5

    def test_block_zero_is_never_handed_out(self):
        p = _pool(num_blocks=4)
        ids = []
        for s in ("a", "b", "c"):
            p.alloc(s, 4)
            ids.extend(p.table(s))
        assert 0 not in ids
        assert sorted(ids) == [1, 2, 3]

    def test_extend_chains_blocks_at_boundaries(self):
        p = _pool(block=4)
        p.alloc("a", 3)
        assert len(p.table("a")) == 1
        p.extend("a")                       # token 4: still block 1
        assert len(p.table("a")) == 1
        p.extend("a")                       # token 5: chains block 2
        assert len(p.table("a")) == 2
        assert p.length("a") == 5

    def test_free_returns_blocks_and_is_idempotent(self):
        p = _pool()
        p.alloc("a", 10)
        before = p.free_blocks
        assert p.free("a") == 3
        assert p.free_blocks == before + 3
        assert p.free("a") == 0             # second free is a no-op
        assert p.live_sequences == 0

    def test_exhaustion_sheds_not_partially_allocates(self):
        p = _pool(num_blocks=4)             # 3 usable
        p.alloc("a", 8)                     # 2 blocks
        free_before = p.free_blocks
        with pytest.raises(PoolExhausted) as ei:
            p.alloc("b", 8)                 # needs 2, only 1 free
        assert ei.value.reason == "kv_pool"
        assert p.free_blocks == free_before     # nothing leaked
        assert telemetry.counter(
            "dl4j_kv_pool_shed_total", "").value(pool="t") >= 1

    def test_extend_exhaustion_raises_for_that_sequence(self):
        p = _pool(num_blocks=3, block=2)    # 2 usable
        p.alloc("a", 4)                     # both blocks
        with pytest.raises(PoolExhausted):
            p.extend("a")
        assert p.length("a") == 4           # length unchanged

    def test_padded_table_is_fixed_width_scratch_padded(self):
        p = _pool(block=4)
        p.alloc("a", 6)
        row = p.padded_table("a", 5)
        assert row.dtype == np.int32 and row.shape == (5,)
        assert list(row[2:]) == [0, 0, 0]   # scratch-block padding

    def test_occupancy_and_gauges_track_alloc_free(self):
        p = _pool(num_blocks=9)             # 8 usable
        p.alloc("a", 16)                    # 4 blocks
        assert p.occupancy == pytest.approx(0.5)
        g = telemetry.gauge("dl4j_kv_pool_blocks", "")
        assert g.value(pool="t", state="live") == 4
        assert g.value(pool="t", state="free") == 4
        p.free("a")
        assert g.value(pool="t", state="live") == 0

    def test_needs_two_blocks_minimum(self):
        with pytest.raises(ValueError):
            _pool(num_blocks=1)


class TestStorage:
    """The arrays as the paged kernel reads them: a token's heads side
    by side down the lanes, no ``head_dim``-wide minor axis."""

    @pytest.mark.parametrize("device_arrays", [False, True],
                             ids=["numpy", "device"])
    def test_k_and_v_are_lane_dense(self, device_arrays):
        p = KVBlockPool(3, 8, 4, 2, 8, name="t-layout",
                        device_arrays=device_arrays)
        assert p.k.shape == p.v.shape == (3, 8, 4, 2 * 8)
        assert p.report()["layout"] == [3, 8, 4, 16]
        # what the gauge and the memory report say is what is stored
        assert p.pool_bytes == 2 * 3 * 8 * 4 * 16 * 4 == \
            p.report()["bytes"]

    def test_arrays_are_k_v_then_the_state_kinds_in_order(self):
        state = {"ssm": ((4, 8, 16), np.float32),
                 "conv": ((3, 10), np.float32)}
        p = _pool(state=state, state_slots=3)
        k, v, ssm, conv = p.arrays
        assert k is p.k and v is p.v
        assert ssm is p.state["ssm"] and conv is p.state["conv"]
        assert ssm.shape == (2, 3, 4, 8, 16) and conv.shape == (2, 3, 3, 10)
        assert p.report()["state"]["layout"] == {
            "ssm": [2, 3, 4, 8, 16], "conv": [2, 3, 3, 10]}
        # update_arrays takes them back in the same order
        new = tuple(a + 1 for a in p.arrays)
        p.update_arrays(*new)
        assert all(a is b for a, b in zip(p.arrays, new))
        assert list(p.state) == ["ssm", "conv"]

    def test_k_v_and_state_are_the_owning_attributes(self):
        """The benchmark frees a pool by ``pool.k = pool.v = None``
        and ``pool.state = {}``: nothing else may hold the arrays."""
        import gc
        import weakref

        import jax.numpy as jnp
        p = KVBlockPool(2, 4, 4, 2, 8, name="t-free",
                        state={"ssm": ((2, 2), jnp.float32)},
                        state_slots=2)
        held = [weakref.ref(a) for a in p.arrays]
        p.k = p.v = None
        p.state = {}
        gc.collect()
        assert all(r() is None for r in held)
        assert p.arrays == (None, None)


class TestMemoryReport:
    def test_pool_is_its_own_resident_class(self):
        p = KVBlockPool(2, 4, 4, 2, 8, name="resident-t")
        rep = diagnostics.memory_report()
        mine = [e for e in rep["kv_pools"]
                if e["pool"] == "resident-t"]
        assert len(mine) == 1
        # [n_layers, blocks, block, heads * head_dim] f32, k + v
        expect = 2 * 4 * 4 * 2 * 8 * 4 * 2
        assert mine[0]["bytes"] == expect
        assert rep["kv_pool_bytes"] >= expect
        # the pool is inside accounted_bytes, not the residual
        assert rep["accounted_bytes"] >= expect
        assert pool_resident_bytes() >= expect
        assert any(e["pool"] == "resident-t" for e in pool_report())

    def test_dropped_pool_leaves_the_report(self):
        import gc
        p = KVBlockPool(1, 2, 2, 1, 4, name="dropme",
                        device_arrays=False)
        assert any(e["pool"] == "dropme" for e in pool_report())
        del p
        gc.collect()
        assert not any(e["pool"] == "dropme" for e in pool_report())
