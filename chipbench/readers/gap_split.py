"""What the host does in the idle gap between two programs of one kind: for every gap
that ``program_gap`` counts (no program of another kind between), the milliseconds of
it that lie under each of the engine's phase spans, and ``unattributed``, the gap less
all of them. A percentile over the gaps, in ms.

A program is paired with the ``dispatch`` span that starts nearest to its own start, and
so with an engine iteration. The gap after the program of iteration i is laid against the
``pull`` and ``emit`` of i and the ``admit``, ``build`` and ``dispatch`` of the iteration
whose program ends the gap: launch latency after ``dispatch`` has returned is under none
of them, and is ``unattributed``.

The device's lines and the host's spans do not share a clock to the millisecond, so the
skew is estimated from the same pairs first: a program cannot start before its
``dispatch`` span starts, and its ``pull`` span cannot end before the program ends. The
offset to add to a device time therefore lies between ``max(dispatch_start -
device_start)`` and ``min(pull_end - device_end)``; the midpoint is taken, and it and the
interval's width go to standard error."""
import bisect
import sys

import numpy as np

from chipbench.readers import ring

FAMILY = "generate."
BEFORE, AFTER = ("pull", "emit"), ("admit", "build", "dispatch")
PHASES = BEFORE + AFTER


def pair(mods, dispatches):
    """``{module start: iter}``: each program with the iteration of the dispatch span
    that starts nearest to it, and no farther from it than half the program's length.
    ``dispatches`` is ``(start, iter)``, in order."""
    starts = [d[0] for d in dispatches]
    out = {}
    for m in mods:
        i = bisect.bisect_left(starts, m[0])
        near = min(dispatches[max(i - 1, 0):i + 1], key=lambda d: abs(d[0] - m[0]), default=None)
        if near is not None and abs(near[0] - m[0]) < 0.5 * (m[1] - m[0]):
            out[m[0]] = near[1]
    return out


def split(view, kind):
    """``{phase: [seconds, one a gap]}`` with ``unattributed`` among them; None where
    there is no gap or no span to lay on it."""
    lo, hi = view["window"]
    ta, tb = view["host_window"]
    mods = [m for m in view["trace"]["devices"][0]["modules"]
            if m[3] != "other" and m[0] >= lo and m[1] <= hi]
    gaps = [(a, b) for a, b in zip(mods, mods[1:]) if a[3] == b[3] == kind]
    # spans a little outside the traced window still lie on its first and last gap
    wide = (ta - (tb - ta), tb + (tb - ta))
    found = {p: ring.inside(view, FAMILY + p, wide) for p in PHASES}
    if not gaps or any(v is None for v in found.values()):
        return None
    of = {p: {} for p in PHASES}            # phase -> iter -> its spans on the profiler's clock
    for p, v in found.items():
        for s, e, a in ring.to_profiler(view, v):
            if p != "dispatch" or a.get("program") == kind:
                of[p].setdefault(a.get("iter"), []).append((s, e))
    mine = [m for m in mods if m[3] == kind]
    iter_of = pair(mine, sorted((s, i) for i, v in of["dispatch"].items() for s, _ in v))
    lower, upper = [], []
    for m in mine:
        i = iter_of.get(m[0])
        if i in of["pull"]:                 # paired, and its pull is in the ring
            lower.append(of["dispatch"][i][0][0] - m[0])
            upper.append(of["pull"][i][-1][1] - m[1])
    if not lower:
        return None
    off, width = (max(lower) + min(upper)) / 2, min(upper) - max(lower)
    print(f"gap_split {kind}: host-device skew {off * 1e3:+.3f} ms "
          f"(interval {width * 1e3:.3f} ms wide, {len(gaps)} gaps)", file=sys.stderr)
    parts = {p: [] for p in PHASES + ("unattributed",)}
    for a, b in gaps:
        s, e = a[1] + off, b[0] + off
        under = {p: sum(max(0.0, min(e, y) - max(s, x))
                        for x, y in of[p].get(iter_of.get((a if p in BEFORE else b)[0]), ()))
                 for p in PHASES}
        for p, v in under.items():
            parts[p].append(v)
        parts["unattributed"].append(e - s - sum(under.values()))
    return parts


def read(view, kind, phase, percentile=50, required=False):
    cache = view.setdefault("_gap_split", {})
    if kind not in cache:
        cache[kind] = split(view, kind)
    parts = cache[kind]
    if parts is None:
        if required and ring.spans(view, FAMILY + "dispatch") is not None:
            raise RuntimeError(f"no {FAMILY}* spans of the program to lay on the {kind} gaps")
        return None
    return float(np.percentile(parts[phase], percentile)) * 1e3
