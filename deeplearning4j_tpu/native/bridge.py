"""ctypes binding + pure-Python fallbacks for the native runtime.

Every public function dispatches to the compiled library when
available and to a numpy implementation otherwise, so callers never
branch. SURVEY.md §2.7 item 4: the host-language↔C++ boundary of the
new stack (ctypes in place of the reference's JavaCPP JNI seam).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import zlib
from typing import Optional, Sequence, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
#: DL4J_TPU_NATIVE_LIB overrides the library path (the sanitizer
#: suite points it at the ASan+UBSan build)
_SO_PATH = os.environ.get(
    "DL4J_TPU_NATIVE_LIB",
    os.path.join(_NATIVE_DIR, "build", "libdl4j_native.so"))

_lib = None
_lock = threading.Lock()
_build_attempted = False
#: how this process got (or did not get) the library — see status()
_status = ("unattempted", "")


def _configure(lib):
    c = ctypes
    lib.dl4j_crc32.restype = c.c_uint32
    lib.dl4j_crc32.argtypes = [c.c_void_p, c.c_int64]
    lib.dl4j_threshold_encode.restype = c.c_int64
    lib.dl4j_threshold_encode.argtypes = [c.c_void_p, c.c_int64,
                                          c.c_float, c.c_void_p,
                                          c.c_int64]
    lib.dl4j_threshold_decode.restype = None
    lib.dl4j_threshold_decode.argtypes = [c.c_void_p, c.c_int64,
                                          c.c_float, c.c_void_p,
                                          c.c_int64]
    lib.dl4j_threshold_residual.restype = None
    lib.dl4j_threshold_residual.argtypes = [c.c_void_p, c.c_void_p,
                                            c.c_int64, c.c_float,
                                            c.c_int64]
    lib.dl4j_arena_create.restype = c.c_void_p
    lib.dl4j_arena_create.argtypes = [c.c_int64]
    lib.dl4j_arena_alloc.restype = c.c_void_p
    lib.dl4j_arena_alloc.argtypes = [c.c_void_p, c.c_int64, c.c_int64]
    lib.dl4j_arena_reset.argtypes = [c.c_void_p]
    lib.dl4j_arena_used.restype = c.c_int64
    lib.dl4j_arena_used.argtypes = [c.c_void_p]
    lib.dl4j_arena_high_water.restype = c.c_int64
    lib.dl4j_arena_high_water.argtypes = [c.c_void_p]
    lib.dl4j_arena_destroy.argtypes = [c.c_void_p]
    lib.dl4j_queue_create.restype = c.c_void_p
    lib.dl4j_queue_create.argtypes = [c.c_int32]
    lib.dl4j_queue_push.restype = c.c_int32
    lib.dl4j_queue_push.argtypes = [c.c_void_p, c.c_size_t, c.c_double]
    lib.dl4j_queue_pop.restype = c.c_int32
    lib.dl4j_queue_pop.argtypes = [c.c_void_p,
                                   c.POINTER(c.c_size_t), c.c_double]
    lib.dl4j_queue_size.restype = c.c_int64
    lib.dl4j_queue_size.argtypes = [c.c_void_p]
    lib.dl4j_queue_close.argtypes = [c.c_void_p]
    lib.dl4j_queue_destroy.argtypes = [c.c_void_p]
    lib.dl4j_parse_csv_floats.restype = c.c_int64
    lib.dl4j_parse_csv_floats.argtypes = [
        c.c_char_p, c.c_int64, c.c_char, c.c_void_p, c.c_int64,
        c.POINTER(c.c_int64), c.POINTER(c.c_int64)]
    lib.dl4j_toposort.restype = c.c_int32
    lib.dl4j_toposort.argtypes = [c.c_void_p, c.c_void_p, c.c_int64,
                                  c.c_int32, c.c_void_p]
    return lib


def _so_mtime() -> float:
    try:
        return os.path.getmtime(_SO_PATH)
    except OSError:
        return -1.0


def ensure_built(force: bool = False) -> bool:
    """Bring the native library up to date and load it (once per
    process). Returns success; :func:`status` says how it went.

    The build always defers to ``make -C native``: a no-op when
    ``native/build/`` is current, a rebuild when ``dl4j_native.cpp`` is
    newer or the directory is missing — so a disk that carries a stale
    ``.so`` and a fresh checkout that carries none end up with the
    same library. A failed build selects the Python fallbacks with a
    warning; a path that must not run on fallbacks checks
    :func:`status` (``chip_smoke.py`` does)."""
    global _lib, _build_attempted, _status
    if os.environ.get("DL4J_TPU_DISABLE_NATIVE"):
        return False
    if _lib is not None and not force:
        # lock-free fast path: every native entry point calls this,
        # so the loaded case must not serialize threads
        return True
    with _lock:
        if _lib is not None and not force:
            return True
        if _build_attempted and not force:
            return False
        _build_attempted = True
        if os.environ.get("DL4J_TPU_NATIVE_LIB"):
            # explicit override: load-or-fail — silently degrading to
            # the Python fallbacks would defeat the point (e.g. a
            # sanitizer run that never touches native code)
            if not os.path.exists(_SO_PATH):
                raise OSError(
                    f"DL4J_TPU_NATIVE_LIB={_SO_PATH} does not exist "
                    f"(build it first, e.g. `make -C native "
                    f"sanitize`)")
            _lib = _configure(ctypes.CDLL(_SO_PATH))
            _status = ("loaded", f"DL4J_TPU_NATIVE_LIB={_SO_PATH}")
            return True
        import logging
        log = logging.getLogger(__name__)
        before = _so_mtime()
        try:
            subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                           capture_output=True, timeout=120)
        except subprocess.CalledProcessError as e:
            err = e.stderr.decode(errors="replace")[-2000:]
            log.warning("native build failed, using Python "
                        "fallbacks:\n%s", err)
            _status = ("absent", f"make failed: {err.strip()[-300:]}")
            return False
        except (OSError, subprocess.TimeoutExpired) as e:
            log.warning("native build unavailable (%s), using Python "
                        "fallbacks", e)
            _status = ("absent", f"make unavailable: {e}")
            return False
        try:
            _lib = _configure(ctypes.CDLL(_SO_PATH))
        except OSError as e:
            _lib = None
            log.warning("native library failed to load (%s), using "
                        "Python fallbacks", e)
            _status = ("absent", f"load failed: {e}")
            return False
        _status = ("built" if _so_mtime() != before else "loaded",
                   _SO_PATH)
        return True


def status() -> Tuple[str, str]:
    """``(state, detail)`` of the native library in this process:
    ``built`` (make compiled it just now), ``loaded`` (make found it
    current), ``absent`` (Python fallbacks in use; detail says why) or
    ``unattempted`` (nothing has asked for it yet). The kill switch
    is read here and not remembered: a process that set it for a while
    (a test of the fallbacks) reports the loaded library again once it
    is unset."""
    if os.environ.get("DL4J_TPU_DISABLE_NATIVE"):
        return ("absent", "DL4J_TPU_DISABLE_NATIVE is set")
    return _status


def available() -> bool:
    return ensure_built()


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


# ---------------------------------------------------------------------------
# CRC32
# ---------------------------------------------------------------------------
def crc32(data) -> int:
    buf = np.ascontiguousarray(
        np.frombuffer(data, np.uint8) if isinstance(data, (bytes,
                                                           bytearray))
        else np.asarray(data).view(np.uint8).ravel())
    if ensure_built():
        return int(_lib.dl4j_crc32(_ptr(buf), buf.size))
    return zlib.crc32(buf.tobytes()) & 0xFFFFFFFF


# ---------------------------------------------------------------------------
# Threshold codec (host side; the in-graph jax version lives in
# parallel/encoding.py — same wire format)
# ---------------------------------------------------------------------------
def threshold_encode(g: np.ndarray, tau: float) -> np.ndarray:
    g = np.ascontiguousarray(np.asarray(g, np.float32).ravel())
    if ensure_built():
        cap = max(16, int(g.size))
        out = np.empty(cap, np.int32)
        k = int(_lib.dl4j_threshold_encode(_ptr(g), g.size,
                                           ctypes.c_float(tau),
                                           _ptr(out), cap))
        return out[:k].copy()
    idx = np.nonzero(np.abs(g) >= tau)[0]
    return ((idx + 1) * np.sign(g[idx])).astype(np.int32)


def threshold_decode(enc: np.ndarray, tau: float, n: int,
                     out: Optional[np.ndarray] = None) -> np.ndarray:
    enc = np.ascontiguousarray(np.asarray(enc, np.int32).ravel())
    if out is None:
        out = np.zeros(n, np.float32)
    elif (out.dtype != np.float32 or not out.flags.c_contiguous
          or out.size < n):
        raise ValueError(
            f"out must be C-contiguous float32 with size >= {n}, got "
            f"{out.dtype} size {out.size} contiguous="
            f"{out.flags.c_contiguous}")
    if ensure_built():
        _lib.dl4j_threshold_decode(_ptr(enc), enc.size,
                                   ctypes.c_float(tau), _ptr(out), n)
        return out
    idx = np.abs(enc) - 1
    np.add.at(out, idx, np.where(enc > 0, tau, -tau).astype(np.float32))
    return out


def threshold_residual(residual: np.ndarray, enc: np.ndarray,
                       tau: float) -> np.ndarray:
    """In-place: residual -= decode(enc); returns residual."""
    residual = np.ascontiguousarray(residual, np.float32)
    enc = np.ascontiguousarray(np.asarray(enc, np.int32).ravel())
    if ensure_built():
        _lib.dl4j_threshold_residual(_ptr(residual), _ptr(enc),
                                     enc.size, ctypes.c_float(tau),
                                     residual.size)
        return residual
    idx = np.abs(enc) - 1
    np.add.at(residual, idx,
              np.where(enc > 0, -tau, tau).astype(np.float32))
    return residual


# ---------------------------------------------------------------------------
# toposort
# ---------------------------------------------------------------------------
def toposort(edges: Sequence[Tuple[int, int]], n_nodes: int):
    """Kahn topological order for (src, dst) edges; raises on cycles."""
    if n_nodes == 0:
        return []
    e = np.asarray(list(edges), np.int32).reshape(-1, 2)
    if ensure_built():
        src = np.ascontiguousarray(e[:, 0])
        dst = np.ascontiguousarray(e[:, 1])
        order = np.empty(n_nodes, np.int32)
        placed = int(_lib.dl4j_toposort(_ptr(src), _ptr(dst),
                                        len(e), n_nodes, _ptr(order)))
        if placed < 0:
            raise ValueError("toposort: edge endpoint out of range")
        if placed < n_nodes:
            raise ValueError("toposort: graph has a cycle")
        return order.tolist()
    indeg = [0] * n_nodes
    adj = [[] for _ in range(n_nodes)]
    for s, d in e.tolist():
        adj[s].append(d)
        indeg[d] += 1
    ready = [i for i in range(n_nodes) if indeg[i] == 0]
    order = []
    for u in ready:
        order.append(u)
        for d in adj[u]:
            indeg[d] -= 1
            if indeg[d] == 0:
                ready.append(d)
    if len(order) < n_nodes:
        raise ValueError("toposort: graph has a cycle")
    return order


# ---------------------------------------------------------------------------
# CSV fast path
# ---------------------------------------------------------------------------
def parse_csv_floats(text, delim: str = ",") -> np.ndarray:
    """Parse delimiter-separated floats into a [rows, cols] array."""
    if isinstance(text, str):
        text = text.encode()
    if ensure_built():
        cap = max(16, text.count(delim.encode()) + text.count(b"\n")
                  + 2)
        out = np.empty(cap, np.float32)
        rows = ctypes.c_int64()
        cols = ctypes.c_int64()
        k = int(_lib.dl4j_parse_csv_floats(
            text, len(text), ctypes.c_char(delim.encode()), _ptr(out),
            cap, ctypes.byref(rows), ctypes.byref(cols)))
        if k == -2:
            raise ValueError("ragged CSV rows")
        if k >= 0:
            return out[:k].reshape(rows.value, cols.value).copy()
        # k == -1 capacity miss -> fall through to python path
    def to_f(x):
        try:
            return float(x)
        except ValueError:     # non-numeric field -> NaN (native
            return float("nan")  # strtof behaves the same way)

    rows = [r for r in text.decode().split("\n") if r.strip()]
    parsed = [[to_f(x) if x.strip() else float("nan")
               for x in r.split(delim)] for r in rows]
    width = {len(r) for r in parsed}
    if len(width) > 1:
        raise ValueError("ragged CSV rows")
    return np.asarray(parsed, np.float32)


# ---------------------------------------------------------------------------
# bounded blocking queue (native pthread ring; Python deque fallback)
# ---------------------------------------------------------------------------
class NativeQueue:
    """Bounded blocking queue of Python objects. Objects park in a
    slot table; only their slot tokens cross the C boundary (same
    opaque-handle style as the reference's JNI buffer ids)."""

    def __init__(self, capacity: int = 8):
        self.capacity = capacity
        self._native = ensure_built()
        self._slots = {}
        self._next_token = [1]
        self._slot_lock = threading.Lock()
        if self._native:
            self._q = _lib.dl4j_queue_create(capacity)
        else:
            import collections
            self._q = collections.deque()
            self._cv = threading.Condition()
            self._closed = False

    def put(self, obj, timeout: Optional[float] = None) -> bool:
        if self._native:
            with self._slot_lock:
                tok = self._next_token[0]
                self._next_token[0] += 1
                self._slots[tok] = obj
            r = _lib.dl4j_queue_push(
                self._q, tok, -1.0 if timeout is None else timeout)
            if r != 1:
                with self._slot_lock:
                    self._slots.pop(tok, None)
                if r == -1:
                    raise RuntimeError("queue closed")
                return False
            return True
        import time
        deadline = None if timeout is None else \
            time.monotonic() + timeout
        with self._cv:
            while len(self._q) >= self.capacity and not self._closed:
                rem = None if deadline is None else \
                    deadline - time.monotonic()
                if rem is not None and rem <= 0:
                    return False
                if not self._cv.wait(rem):
                    return False
            if self._closed:
                raise RuntimeError("queue closed")
            self._q.append(obj)
            self._cv.notify_all()
            return True

    def get(self, timeout: Optional[float] = None):
        """Returns the object, or raises queue.Empty on timeout /
        StopIteration when closed and drained."""
        import queue as _pyqueue
        if self._native:
            tok = ctypes.c_size_t()
            r = _lib.dl4j_queue_pop(
                self._q, ctypes.byref(tok),
                -1.0 if timeout is None else timeout)
            if r == 0:
                raise _pyqueue.Empty()
            if r == -1:
                raise StopIteration()
            with self._slot_lock:
                return self._slots.pop(tok.value)
        import time
        deadline = None if timeout is None else \
            time.monotonic() + timeout
        with self._cv:
            while not self._q and not self._closed:
                rem = None if deadline is None else \
                    deadline - time.monotonic()
                if rem is not None and rem <= 0:
                    raise _pyqueue.Empty()
                if not self._cv.wait(rem):
                    raise _pyqueue.Empty()
            if self._q:
                obj = self._q.popleft()
                self._cv.notify_all()
                return obj
            raise StopIteration()

    def qsize(self) -> int:
        if self._native:
            return int(_lib.dl4j_queue_size(self._q))
        with self._cv:
            return len(self._q)

    def close(self):
        if self._native:
            _lib.dl4j_queue_close(self._q)
        else:
            with self._cv:
                self._closed = True
                self._cv.notify_all()

    def __del__(self):
        try:
            if self._native and _lib is not None:
                _lib.dl4j_queue_destroy(self._q)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# arena
# ---------------------------------------------------------------------------
class arena:
    """Workspace-style host staging arena (context manager).

    With the native lib, allocations live in one malloc'd block and
    ``reset()`` is O(1) — the reference's MemoryWorkspace behavior.
    Fallback allocates numpy arrays (still scope-tracked)."""

    def __init__(self, capacity_bytes: int = 1 << 20):
        self.capacity = capacity_bytes
        self._native = ensure_built()
        self._handle = (_lib.dl4j_arena_create(capacity_bytes)
                        if self._native else None)
        self._spill = []

    def alloc(self, shape, dtype=np.float32) -> np.ndarray:
        dtype = np.dtype(dtype)
        size = int(np.prod(shape)) * dtype.itemsize
        if self._native:
            p = _lib.dl4j_arena_alloc(self._handle, size, 64)
            if p:
                buf = (ctypes.c_char * size).from_address(p)
                # keep the arena alive while any view escapes: the
                # array's base chain reaches buf, and buf pins the
                # arena (else __del__ would free() under live views)
                buf._owner = self
                return np.frombuffer(buf, dtype).reshape(shape)
        a = np.empty(shape, dtype)
        self._spill.append(a)
        return a

    def reset(self):
        if self._native:
            _lib.dl4j_arena_reset(self._handle)
        self._spill.clear()

    @property
    def used(self) -> int:
        return (int(_lib.dl4j_arena_used(self._handle))
                if self._native else
                sum(a.nbytes for a in self._spill))

    @property
    def high_water(self) -> int:
        return (int(_lib.dl4j_arena_high_water(self._handle))
                if self._native else self.used)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.reset()
        return False

    def __del__(self):
        try:
            if self._native and _lib is not None and self._handle:
                _lib.dl4j_arena_destroy(self._handle)
                self._handle = None
        except Exception:
            pass
