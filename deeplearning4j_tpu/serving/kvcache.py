"""Paged KV-cache residency for generative serving.

The decode phase of autoregressive inference is bound by KV-cache
memory, not FLOPs: every live sequence keeps ``2 * layers * len *
heads * head_dim`` activations resident between tokens. Allocating
that per-request as contiguous max-length tensors wastes HBM on the
gap between a sequence's current length and its ``max_tokens`` —
the fragmentation paged attention (vLLM) eliminates. This module is
that allocator for the TPU stack:

- One preallocated device array pair per pool — ``k`` / ``v`` shaped
  ``[n_layers, num_blocks, block_size, n_heads * head_dim]`` (``v``
  ``n_heads * v_head_dim`` lanes where a model's value heads have a
  width of their own) — carved
  into fixed-size **blocks** of ``block_size`` token slots. That is
  the form the paged decode kernel reads: a token's heads side by
  side down the lanes (no ``head_dim``-wide minor axis for the
  compiler to pad to a lane tile), a block one contiguous
  ``[block, n_heads * head_dim]`` DMA at ``k[l, table[j]]``. No
  program slices a layer out of it or relays it.
- A **block table** per sequence: the ordered list of block ids
  holding its tokens. Block ids are shared across layers (layer ``l``
  of token ``t`` lives at ``k[l, table[t // block_size],
  t % block_size]``, head ``h`` on lanes ``[h * head_dim, (h + 1) *
  head_dim)``), so the table is one small int array per sequence, not
  one per layer.
- **Block 0 is reserved scratch**: padded decode-batch rows (slots
  with no live sequence) write their dummy KV there, so the fused
  step never branches on liveness for the write. It is never handed
  to a sequence.
- alloc/extend/free with occupancy accounting: gauges
  ``dl4j_kv_pool_blocks{state=free|live}`` / ``dl4j_kv_pool_bytes``,
  exhaustion counted into ``dl4j_kv_pool_shed_total`` and raised as
  :class:`PoolExhausted` (a :class:`ShedError` — HTTP 429 with a
  drain-rate-measured ``Retry-After`` upstream).

A model whose blocks also carry **recurrent state** (a state-space
mixer beside attention) gets a second kind of residency from the same
manager: ``state={name: (shape, dtype)}`` adds one device array a kind
``[n_layers, state_slots, *shape]``, a **slot** a live sequence. A
slot's content is the running summary of the whole sequence: it cannot
be re-gathered from blocks, so it is written at admission (the
prefill's state at the prompt's last token) and released at
retirement. **Slot 0 is scratch** as block 0 is (dead decode rows
write there). ``n_heads`` is the KV head count: a grouped-query model
passes fewer KV heads than it has query heads. Gauges
``dl4j_state_pool_slots{state=free|live}`` / ``dl4j_state_pool_bytes``.

A model whose layers do not all keep every token gets a **third kind**:
the K/V arrays hold only its layers that grow with the context
(``n_layers`` is their count, one table a sequence as ever), and each
**window layer** keeps a **ring** of its last ``window`` positions in
the sequence's slot, a slot kind written ``{"shape": (window, lanes),
"dtype": None, "layers": n, "window": window}`` (``dtype`` None: the
K/V type; ``layers``: this kind's own layer count, which a recurrent
kind may name too). A ring is stored as the paged kernel reads a pool,
``[layers, slots, window / block, block, lanes]``: slot ``s`` is blocks
``[s * window / block, ...)`` of ``[layers, slots * window / block,
block, lanes]``, a merge of leading axes. Its bytes a sequence are
constant: position ``p`` lives at ``p mod window``.
:attr:`KVBlockPool.window_bytes` and ``dl4j_window_pool_bytes`` count
the rings; ``state_bytes`` and its gauge the recurrent kinds.

The pool's device bytes are a first-class **resident class** in
``diagnostics.memory_report`` (next to params / updater state), looked
up lazily via ``sys.modules`` so diagnostics keeps zero import edges
into serving. ``pool_report()`` is that join point; the report numbers
reconcile exactly with the gauges (same ``nbytes`` source).
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.common import telemetry
from deeplearning4j_tpu.serving.admission import ShedError

#: live pools, for memory_report / pool_report (weak: a retired pool
#: must not be kept resident by the diagnostics join)
_pools: "weakref.WeakSet[KVBlockPool]" = weakref.WeakSet()


class PoolExhausted(ShedError):
    """The KV pool has no free block for an alloc/extend — the
    generative analog of a full admission queue: shed (HTTP 429) with
    a measured ``Retry-After`` instead of queueing unboundedly."""

    def __init__(self, retry_after_s: float = 1.0):
        super().__init__("kv_pool", retry_after_s)


def _blocks_gauge() -> telemetry.Gauge:
    return telemetry.gauge(
        "dl4j_kv_pool_blocks",
        "KV-cache pool blocks by state (free | live) per pool — "
        "occupancy = live / (live + free); block 0 is reserved "
        "scratch and counted in neither state")


def _bytes_gauge() -> telemetry.Gauge:
    return telemetry.gauge(
        "dl4j_kv_pool_bytes",
        "preallocated device bytes of a KV-cache pool (k + v arrays; "
        "constant for the pool's lifetime — paged residency means "
        "occupancy moves, allocation does not)")


def _slots_gauge() -> telemetry.Gauge:
    return telemetry.gauge(
        "dl4j_state_pool_slots",
        "recurrent-state slots by state (free | live) per pool — one "
        "slot a live sequence of a model with state-space layers; "
        "slot 0 is reserved scratch and counted in neither state")


def _state_bytes_gauge() -> telemetry.Gauge:
    return telemetry.gauge(
        "dl4j_state_pool_bytes",
        "preallocated device bytes of a pool's recurrent-state arrays "
        "(all kinds, all slots; constant for the pool's lifetime)")


def _window_bytes_gauge() -> telemetry.Gauge:
    return telemetry.gauge(
        "dl4j_window_pool_bytes",
        "preallocated device bytes of a pool's window rings (K and V, "
        "every window layer, all slots; constant whatever the contexts)")


def _shed_counter() -> telemetry.Counter:
    return telemetry.counter(
        "dl4j_kv_pool_shed_total",
        "generative requests shed because the KV pool had no free "
        "block (HTTP 429 + measured Retry-After upstream)")


class KVBlockPool:
    """A paged KV-cache pool: preallocated k/v device arrays plus the
    host-side block allocator.

    ``alloc(seq_id, n_tokens)`` reserves the block-table for a new
    sequence, ``extend(seq_id)`` grows it one token (chaining a new
    block at each ``block_size`` boundary), ``free(seq_id)`` returns
    every block to the free list — callable mid-batch, which is the
    whole point of iteration-level scheduling. The device arrays are
    **donated** to every program that writes them: the engine's commit
    and decode programs take :attr:`arrays`, update them in place, and
    the engine stores what they return back with :meth:`update_arrays`
    (the arrays it passed in are gone from then on).
    """

    def __init__(self, n_layers: int, num_blocks: int,
                 block_size: int, n_heads: int, head_dim: int, *,
                 dtype=np.float32, name: str = "model",
                 device_arrays: bool = True,
                 state: Optional[dict] = None, state_slots: int = 0,
                 v_head_dim: Optional[int] = None):
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is "
                             "reserved scratch)")
        if state and state_slots < 2:
            raise ValueError("state_slots must be >= 2 (slot 0 is "
                             "reserved scratch)")
        self.n_layers = int(n_layers)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        #: a value head's lanes: the key head's unless the model says
        self.v_head_dim = int(v_head_dim or head_dim)
        self.name = name
        shape = (self.n_layers, self.num_blocks, self.block_size,
                 self.n_heads * self.head_dim)
        v_shape = shape[:3] + (self.n_heads * self.v_head_dim,)
        if device_arrays:
            import jax.numpy as jnp
            self.k = jnp.zeros(shape, dtype=dtype)
            self.v = jnp.zeros(v_shape, dtype=dtype)
        else:               # allocator-only pool (tests, sizing math)
            self.k = np.zeros(shape, dtype=dtype)
            self.v = np.zeros(v_shape, dtype=dtype)
        #: slot arrays by kind, [layers, slots, *shape]: recurrent
        #: state, and the rings of window layers
        self.state_slots = int(state_slots) if state else 0
        xp = jnp if device_arrays else np
        self.state = {}
        #: the kinds that are window rings
        self.window_kinds: set = set()
        for kind, spec in (state or {}).items():
            if not isinstance(spec, dict):
                spec = {"shape": spec[0], "dtype": spec[1]}
            shp = tuple(spec["shape"])
            if spec.get("window"):
                if shp[0] % self.block_size:
                    raise ValueError(
                        f"window {shp[0]} is not whole blocks of "
                        f"{self.block_size}")
                shp = (shp[0] // self.block_size, self.block_size) + shp[1:]
                self.window_kinds.add(kind)
            self.state[kind] = xp.zeros(
                (int(spec.get("layers") or self.n_layers),
                 self.state_slots) + shp,
                dtype=spec["dtype"] if spec.get("dtype") is not None
                else dtype)
        self._lock = threading.RLock()
        #: free state slots, LIFO (slot 0 reserved)
        self._free_slots: List[int] = list(
            range(self.state_slots - 1, 0, -1))
        self._slots: Dict[object, int] = {}
        #: free block ids, LIFO (block 0 reserved — see module doc)
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._tables: Dict[object, List[int]] = {}
        self._lengths: Dict[object, int] = {}
        _pools.add(self)
        if telemetry.enabled():
            _bytes_gauge().set(self.pool_bytes, pool=self.name)
            self._export_occupancy()
            if self.state:
                _state_bytes_gauge().set(self.state_bytes, pool=self.name)
                self._export_slots()
            if self.window_kinds:
                _window_bytes_gauge().set(self.window_bytes,
                                          pool=self.name)

    # -- sizing ---------------------------------------------------------
    @property
    def pool_bytes(self) -> int:
        """Preallocated device bytes (k + v) — the resident class.
        The arrays' own ``nbytes``: with a token's heads merged down
        the lanes that is what the device holds (no ``head_dim`` minor
        axis padded to a lane tile) wherever ``n_heads * head_dim`` is
        whole 128-lane tiles and a block whole sublane tiles, and the
        donated programs never hold a second copy."""
        return int(self.k.nbytes) + int(self.v.nbytes)

    @property
    def state_bytes(self) -> int:
        """Preallocated device bytes of the recurrent-state arrays."""
        return sum(int(a.nbytes) for kind, a in self.state.items()
                   if kind not in self.window_kinds)

    @property
    def window_bytes(self) -> int:
        """Preallocated device bytes of the window layers' rings: a
        fixed share a slot, whatever the contexts."""
        return sum(int(self.state[kind].nbytes) for kind in self.window_kinds)

    @property
    def arrays(self) -> tuple:
        """Every device array of the cache as one pytree, in the order
        the model's ``decode_step`` takes and returns them: ``(k, v)``,
        then the state arrays in the order of their kinds."""
        return (self.k, self.v) + tuple(self.state.values())

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1          # minus the scratch block

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks a sequence of ``n_tokens`` occupies (ceil)."""
        return max(1, -(-int(n_tokens) // self.block_size))

    # -- occupancy ------------------------------------------------------
    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def live_blocks(self) -> int:
        with self._lock:
            return sum(len(t) for t in self._tables.values())

    @property
    def occupancy(self) -> float:
        """live / usable, in [0, 1]."""
        return self.live_blocks / max(1, self.usable_blocks)

    @property
    def live_sequences(self) -> int:
        with self._lock:
            return len(self._tables)

    def _export_occupancy(self) -> None:
        if not telemetry.enabled():
            return
        g = _blocks_gauge()
        # a block is free or in a table: live is one subtraction, not
        # a sum over every table (this runs on every alloc/extend/free)
        g.set(len(self._free), pool=self.name, state="free")
        g.set(self.usable_blocks - len(self._free),
              pool=self.name, state="live")

    # -- state slots ----------------------------------------------------
    @property
    def usable_slots(self) -> int:
        return max(0, self.state_slots - 1)     # minus the scratch slot

    @property
    def free_slots(self) -> int:
        with self._lock:
            return len(self._free_slots)

    def _export_slots(self) -> None:
        if not telemetry.enabled():
            return
        g = _slots_gauge()
        g.set(len(self._free_slots), pool=self.name, state="free")
        g.set(len(self._slots), pool=self.name, state="live")

    def alloc_slot(self, seq_id) -> Optional[int]:
        """A state slot for a sequence that is being admitted, or None
        when every slot is live: the caller keeps the request queued
        (a slot frees at the next retirement), it is not shed."""
        with self._lock:
            if seq_id in self._slots:
                raise ValueError(f"sequence {seq_id!r} already has a "
                                 f"state slot")
            if not self._free_slots:
                return None
            self._slots[seq_id] = self._free_slots.pop()
            self._export_slots()
            return self._slots[seq_id]

    def slot(self, seq_id) -> int:
        """The sequence's state slot (0, the scratch slot, if none)."""
        with self._lock:
            return self._slots.get(seq_id, 0)

    # -- lifecycle ------------------------------------------------------
    def alloc(self, seq_id, n_tokens: int) -> List[int]:
        """Reserve blocks for a new sequence of ``n_tokens`` prompt
        tokens. Raises :class:`PoolExhausted` (counting the shed)
        without partial allocation when the pool cannot hold it."""
        need = self.blocks_for(n_tokens)
        with self._lock:
            if seq_id in self._tables:
                raise ValueError(f"sequence {seq_id!r} already has a "
                                 f"block table")
            if need > len(self._free):
                _shed_counter().inc(pool=self.name)
                raise PoolExhausted()
            blocks = [self._free.pop() for _ in range(need)]
            self._tables[seq_id] = blocks
            self._lengths[seq_id] = int(n_tokens)
            self._export_occupancy()
            return list(blocks)

    def extend(self, seq_id, n_tokens: int = 1) -> List[int]:
        """Grow a sequence by ``n_tokens`` (decode appends one per
        step), chaining new block-table entries across ``block_size``
        boundaries. Returns the current table. On exhaustion raises
        :class:`PoolExhausted` with the sequence's existing blocks
        intact (the caller decides whether to retire it)."""
        with self._lock:
            if seq_id not in self._tables:
                raise KeyError(f"unknown sequence {seq_id!r}")
            new_len = self._lengths[seq_id] + int(n_tokens)
            need = self.blocks_for(new_len) - len(self._tables[seq_id])
            if need > len(self._free):
                _shed_counter().inc(pool=self.name)
                raise PoolExhausted()
            for _ in range(need):
                self._tables[seq_id].append(self._free.pop())
            self._lengths[seq_id] = new_len
            if need:
                self._export_occupancy()
            return list(self._tables[seq_id])

    def free(self, seq_id) -> int:
        """Return a sequence's blocks, and its state slot if it holds
        one, to the pool (EOS / max_tokens / client disconnect — all
        mid-batch paths). Idempotent; returns the number of blocks
        released."""
        with self._lock:
            slot = self._slots.pop(seq_id, None)
            if slot is not None:
                self._free_slots.append(slot)
                self._export_slots()
            blocks = self._tables.pop(seq_id, None)
            self._lengths.pop(seq_id, None)
            if not blocks:
                return 0
            self._free.extend(reversed(blocks))
            self._export_occupancy()
            return len(blocks)

    def table(self, seq_id) -> List[int]:
        with self._lock:
            return list(self._tables[seq_id])

    def length(self, seq_id) -> int:
        with self._lock:
            return self._lengths[seq_id]

    def padded_table(self, seq_id, max_blocks: int) -> np.ndarray:
        """The sequence's block table as a fixed-width int32 row
        (padded with the scratch block 0) — the shape-stable form the
        jitted decode step consumes."""
        t = self.table(seq_id)
        if len(t) > max_blocks:
            raise ValueError(f"sequence {seq_id!r} spans {len(t)} "
                             f"blocks > table width {max_blocks}")
        return np.asarray(t + [0] * (max_blocks - len(t)), np.int32)

    def update_arrays(self, k, v, *state) -> None:
        """Store the arrays a commit or decode program returned (the
        donated buffers, updated in place), in the order of
        :attr:`arrays`."""
        self.k, self.v = k, v
        if state:
            self.state = dict(zip(self.state, state))

    def report(self) -> dict:
        """The memory_report join row for this pool."""
        state = {}
        if self.state:
            state = {"state": {
                "bytes": self.state_bytes,
                "slots": {"free": self.free_slots,
                          "live": self.usable_slots - self.free_slots,
                          "reserved": 1, "total": self.state_slots},
                "layout": {k: list(a.shape)
                           for k, a in self.state.items()
                           if k not in self.window_kinds}}}
        if self.window_kinds:
            state["window"] = {
                "bytes": self.window_bytes,
                "bytes_per_slot": self.window_bytes // self.state_slots,
                "layout": {k: list(self.state[k].shape)
                           for k in sorted(self.window_kinds)}}
        if self.v_head_dim != self.head_dim:
            state["layout_v"] = list(self.v.shape)
        return {
            **state,
            "pool": self.name,
            "bytes": self.pool_bytes,
            "blocks": {"free": self.free_blocks,
                       "live": self.live_blocks,
                       "reserved": 1,
                       "total": self.num_blocks},
            "occupancy": round(self.occupancy, 4),
            "live_sequences": self.live_sequences,
            "block_tokens": self.block_size,
            "layout": [self.n_layers, self.num_blocks, self.block_size,
                       self.n_heads * self.head_dim],
        }


def pool_report() -> List[dict]:
    """Reports for every live pool — the ``kv_pools`` resident class
    ``diagnostics.memory_report`` joins in (lazy ``sys.modules``
    lookup on its side; no import edge)."""
    return sorted((p.report() for p in list(_pools)),
                  key=lambda r: r["pool"])


def pool_resident_bytes() -> int:
    """Total preallocated KV bytes across live pools (the number that
    must reconcile with the summed ``dl4j_kv_pool_bytes`` gauge)."""
    return sum(p.pool_bytes for p in list(_pools))
