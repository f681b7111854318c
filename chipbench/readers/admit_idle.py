"""The device's idle time round an admission, from the device's trace alone. On the device
an admission is the cluster of programs of other kinds (prefill, commit and first-token
sample, one such triple a request admitted, and the merge of first tokens into a step)
between two programs of ``kind`` (the decode step); its idle time is the complement of the
union of ``XLA Ops`` from the end of the step before the cluster to the start of the step
after it. A percentile over the clusters that lie whole inside the traced window, in ms.

``phase`` splits a cluster's idle by the device's own edges: ``before`` the cluster's first
program starts, ``after`` its last program ends, and ``within``, the rest (between one
request's programs and the next one's where an admission took several, and inside the
programs). Where the admission joins the queued steps, its programs are dispatched behind
the steps in flight and the next step behind them, so ``before`` and ``after`` are launch
gaps of microseconds; where an engine lands its queue first, ``before`` holds the host's
hand-out of the last step and the prefill's dispatch, and ``after`` the first token's pull
and a step built afresh. The three add up to the cluster's idle; medians need not. The
host's spans could not split it finer: the pairs that bound the skew between the two clocks
at an admission leave 1.8-2.5 ms (a launch one way, a completion the other), as wide as the
pieces."""
import bisect
import sys

import numpy as np

from chipbench import trace as tr

PHASES = ("before", "within", "after")


def clusters(dev, kind, lo, hi):
    """``(step's end, next step's start, [programs between])`` of every admission of the
    window that has a program of ``kind`` on both sides."""
    out, run, before = [], [], None
    for m in dev["modules"]:
        if m[3] == "other" or m[0] < lo or m[1] > hi:
            continue
        if m[3] != kind:
            run.append(m)
            continue
        if run and before is not None:
            out.append((before[1], m[0], run))
        before, run = m, []
    return out


def split(view, kind):
    """``{"total": [seconds, one an admission], "before": [...], "within": [...],
    "after": [...]}``, or None with no admission in the window."""
    lo, hi = view["window"]
    dev = view["trace"]["devices"][0]
    found = clusters(dev, kind, lo, hi)
    if not found:
        return None
    busy = tr.union(dev["ops"], lo, hi)
    idle = [(a[1], b[0]) for a, b in zip([[lo, lo]] + busy, busy + [[hi, hi]]) if b[0] > a[1]]
    starts = [s for s, _ in idle]

    def idle_in(a, b):
        # the gaps are disjoint and in order: only those from the one holding a to b count
        near = idle[max(bisect.bisect_right(starts, a) - 1, 0):bisect.bisect_left(starts, b)]
        return sum(max(0.0, min(e, b) - max(s, a)) for s, e in near)

    parts = {p: [] for p in ("total",) + PHASES}
    for a_end, b_start, mods in found:
        first, last = mods[0][0], max(m[1] for m in mods)
        got = {"total": idle_in(a_end, b_start), "before": idle_in(a_end, first),
               "after": idle_in(last, b_start)}
        got["within"] = got["total"] - got["before"] - got["after"]
        for p, v in got.items():
            parts[p].append(v)
    print(f"admit_idle {kind}: {len(found)} admissions of {sum(len(c[2]) for c in found)} programs "
          f"idle {sum(parts['total']):.4f} s of the window's {sum(e - s for s, e in idle):.4f} s; "
          + ", ".join(f"{p} {sum(parts[p]):.4f}" for p in PHASES) + " s", file=sys.stderr)
    return parts


def read(view, kind, phase="total", percentile=50):
    cache = view.setdefault("_admit_idle", {})
    if kind not in cache:
        cache[kind] = split(view, kind)
    parts = cache[kind]
    if parts is None or phase not in parts:
        return None
    return float(np.percentile(parts[phase], percentile)) * 1e3
