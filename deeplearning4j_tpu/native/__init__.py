"""Native host-runtime bridge (ctypes over ``native/dl4j_native.cpp``).

The reference reaches its native engine over a flat C ABI
(`NativeOps.h` + JavaCPP JNI — SURVEY.md N14/J4). Here the seam is
ctypes over a small C ABI: no JNI, no codegen, and every entry point
has a pure-Python fallback so the package works before/without the
compiled library (set ``DL4J_TPU_DISABLE_NATIVE=1`` to force the
fallbacks).

The first native call of a process runs ``make -C native`` (a no-op
when ``native/build/libdl4j_native.so`` is current) and loads the
result; ``status()`` says whether it was built, loaded or is absent.
"""
from .bridge import (NativeQueue, arena, available, crc32, ensure_built,
                     parse_csv_floats, status, threshold_decode,
                     threshold_encode, threshold_residual, toposort)

__all__ = ["available", "ensure_built", "status", "crc32",
           "threshold_encode",
           "threshold_decode", "threshold_residual", "toposort",
           "parse_csv_floats", "NativeQueue", "arena"]
