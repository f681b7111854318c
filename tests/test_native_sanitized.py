"""Run the ENTIRE native test suite against the ASan+UBSan build
(SURVEY.md §5.2 / N16; round-2 verdict ask #8): the C++ runtime does
pointer arithmetic, arena math, and a pthread ring queue — the
sanitizers must see every code path the normal suite exercises.

Mechanics: ``make sanitize`` produces ``libdl4j_native_san.so``; a
subprocess re-runs tests/test_native.py with libasan LD_PRELOADed and
``DL4J_TPU_NATIVE_LIB`` pointing at the sanitized library
(``-fno-sanitize-recover=all``, halt-on-error, so any finding fails
the run).  Leak detection is off — the host is a full CPython
interpreter."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE = os.path.join(_ROOT, "native")
_SAN_LIB = os.path.join(_NATIVE, "build", "libdl4j_native_san.so")


def _libasan_path():
    try:
        out = subprocess.run(["g++", "-print-file-name=libasan.so"],
                             capture_output=True, text=True,
                             timeout=30)
        path = out.stdout.strip()
        return path if path and os.path.exists(path) else None
    except Exception:
        return None


def test_native_suite_under_asan_ubsan():
    libasan = _libasan_path()
    if libasan is None:
        pytest.skip("libasan not available")
    build = subprocess.run(["make", "-C", _NATIVE, "sanitize"],
                           capture_output=True, text=True,
                           timeout=300)
    assert build.returncode == 0, build.stderr[-2000:]
    assert os.path.exists(_SAN_LIB)

    env = {
        **os.environ,
        "PYTHONPATH": _ROOT,
        "JAX_PLATFORMS": "cpu",
        "LD_PRELOAD": libasan,
        "DL4J_TPU_NATIVE_LIB": _SAN_LIB,
        # CPython itself is not leak-clean; every real ASan/UBSan
        # finding still aborts via -fno-sanitize-recover=all
        "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1:"
                        "allocator_may_return_null=1",
        "UBSAN_OPTIONS": "halt_on_error=1:print_stacktrace=1",
    }
    # -k: jaxlib is not ASan-instrumented and crashes under the
    # preload; test_streams_all_batches is the one case that imports
    # jax (via DataSet) — the native ring queue it rides on is fully
    # covered by TestQueue, which runs here
    run = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(_ROOT, "tests", "test_native.py"), "-q",
         "--no-header", "-p", "no:cacheprovider",
         "-k", "not streams_all_batches"],
        capture_output=True, text=True, timeout=480, env=env,
        cwd=_ROOT)
    tail = (run.stdout + "\n" + run.stderr)[-4000:]
    assert run.returncode == 0, \
        f"native suite under ASan+UBSan failed:\n{tail}"
    assert "passed" in run.stdout, tail
