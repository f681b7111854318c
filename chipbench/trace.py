"""The one reduction from a profiler trace (``.xplane.pb``) to times, with nothing
but JAX. A device plane is ``/device:TPU:<n>``; its line ``XLA Modules`` has one event
for every program execution and ``XLA Ops`` one for every operation. Host spans are the
``cb.*`` ``TraceAnnotation`` events of the benchmark's own drivers. Seconds throughout."""
import bisect
import glob
import os
import re


def short(op_name):
    """``%pallas.bn_bwd.20 = f32[..] custom-call(..)`` -> ``pallas.bn_bwd``."""
    return re.sub(r"[.\d]+$", "", op_name.split(" = ", 1)[0].lstrip("%")) or op_name


def _events(line):
    return sorted((e.start_ns * 1e-9, e.duration_ns * 1e-9, e.name) for e in line.events)


def read(path, programs=()):
    """``{"devices": [{"modules": [...], "ops": [...]}], "spans": {name: [(start, end)]}}``.
    A module is ``(start, end, name, kind)`` with the kind that ``kinds`` gives its name; an
    op is ``(start, end, name)``."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))[-1]
    data = ProfileData.from_file(path)
    devices, spans = [], {}
    for plane in sorted(data.planes, key=lambda p: p.name):
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: _events(ln) for ln in plane.lines
                     if ln.name in ("XLA Modules", "XLA Ops")}
            ops = [(s, s + d, n) for s, d, n in lines.get("XLA Ops", [])]
            runs = [(s, s + d, n) for s, d, n in lines.get("XLA Modules", [])]
            kind = kinds(runs, ops, programs)
            devices.append({"modules": [(s, e, n, kind[n]) for s, e, n in runs], "ops": ops})
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("cb."):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    return {"devices": devices, "spans": spans}


def kinds(runs, ops, programs):
    """The kind of every module name. Each entry of ``programs`` in turn claims the names
    that no earlier one has: those that its ``module`` pattern matches and, if it gives an
    ``op`` pattern, that ran an operation matching it. With ``"pick": "most_runs"`` it
    claims of those only the one executed most often: an engine runs its decode step once
    an iteration and every other program once a request, whatever the step is lowered to.
    Names that nothing claims are ``other``."""
    starts = [o[0] for o in ops]
    count, first = {}, {}
    for s, e, n in runs:
        count[n] = count.get(n, 0) + 1
        first.setdefault(n, (s, e))
    kind = {}
    for p in programs:
        mine = [n for n in count if n not in kind and re.search(p["module"], n)]
        if "op" in p:
            inside = lambda n: ops[bisect.bisect_left(starts, first[n][0]):  # noqa: E731
                                   bisect.bisect_right(starts, first[n][1])]
            mine = [n for n in mine if any(re.search(p["op"], short(o[2])) for o in inside(n))]
        if p.get("pick") == "most_runs":
            mine = sorted(mine, key=lambda n: -count[n])[:1]
        kind.update({n: p["kind"] for n in mine})
    return {n: kind.get(n, "other") for n in count}


def union(intervals, lo, hi):
    """Merged pieces of ``intervals`` clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(device, lo, hi):
    return sum(e - s for s, e in union(device["ops"], lo, hi))


def op_seconds(device, pattern, lo, hi, kind=None):
    """Summed device time inside the window of the operations whose short name matches;
    with ``kind``, only those inside a module of that kind."""
    mods = device["modules"]
    starts = [m[0] for m in mods]
    total = 0.0
    for s, e, n in device["ops"]:
        if s >= lo and e <= hi and re.search(pattern, short(n)):
            i = bisect.bisect_right(starts, s) - 1
            if kind is None or (i >= 0 and s < mods[i][1] and mods[i][3] == kind):
                total += e - s
    return total


def modules_of(device, kind, lo, hi):
    return [m for m in device["modules"] if m[3] == kind and m[0] >= lo and m[1] <= hi]


def idle_gaps(device, lo, hi):
    """Every idle gap of the window as ``(seconds, label)``: ``in_<kind>`` inside a
    program, ``between_<kind>s`` between two of one kind, else ``<kind>_to_<kind>``.
    Modules of kind ``other`` (a scalar convert, a key fold) do not break a pair."""
    named = [m for m in device["modules"] if m[3] != "other"]
    starts = [m[0] for m in named]
    gaps, at = [], lo
    for s, e in union(device["ops"], lo, hi) + [[hi, hi]]:
        if s > at:
            mid = (at + s) / 2
            i = bisect.bisect_right(starts, mid) - 1
            before = named[i] if i >= 0 else None
            after = named[i + 1] if i + 1 < len(named) else None
            if before and before[0] <= mid < before[1]:
                label = f"in_{before[3]}"
            elif before and after and before[3] == after[3]:
                label = f"between_{before[3]}s"
            else:
                label = f"{before[3] if before else 'start'}_to_{after[3] if after else 'end'}"
            gaps.append((s - at, label))
        at = max(at, e)
    return gaps


def breakdown(device, lo, hi, top=10):
    ops, gaps = {}, {}
    for s, e, n in device["ops"]:
        if s >= lo and e <= hi:
            ops[short(n)] = ops.get(short(n), 0.0) + e - s
    for sec, label in idle_gaps(device, lo, hi):
        gaps[label] = gaps.get(label, 0.0) + sec

    def rank(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}
