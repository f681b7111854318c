"""The program's own spans, read in process from its telemetry ring
(``telemetry.trace_events()``, which holds the whole run) and put on the clocks the
benchmark already has. The ring stamps epoch microseconds derived from ``perf_counter``
through one anchor, and ``telemetry.perf_counter_of`` is the way back: a span then sits
on the clock the drivers stamp ``host_window`` and every token with, and
``lo + (t - ta)`` puts it on the profiler's, as ``share._arrivals`` places a token.

A program from before that clock (no ``perf_counter_of``) has nothing these readers can
place: ``spans`` gives None and every reader returns nothing. With the clock there, a
reader called with ``required`` raises where it finds no span to read: the engine's
spans have gone, and the metric may not fall silent with them."""


def spans(view, name):
    """``(start, end, args)`` of every complete span of that name, in ``perf_counter``
    seconds and in order. The ring is read once a view, into ``view["ring"]`` (which the
    tests fill by hand): ``(start, end, name, args)`` of every span, or None."""
    if "ring" not in view:
        from deeplearning4j_tpu.common import telemetry
        to_perf = getattr(telemetry, "perf_counter_of", None)
        view["ring"] = to_perf and [
            (to_perf(e["ts"]), to_perf(e["ts"] + e["dur"]), e["name"], e["args"])
            for e in telemetry.trace_events() if e.get("ph") == "X"]
    if view["ring"] is None:
        return None
    return sorted(((s, e, a) for s, e, n, a in view["ring"] if n == name), key=lambda x: x[:2])


def inside(view, name, window, required=False):
    """The spans of that name that lie whole inside ``window`` (``perf_counter``)."""
    found = spans(view, name)
    if found is None:
        return None
    lo, hi = window
    found = [x for x in found if lo <= x[0] and x[1] <= hi]
    if required and not found:
        raise RuntimeError(f"no {name} span of the program lies in the window")
    return found


def to_profiler(view, found):
    """The same spans on the profiler's clock."""
    (ta, _), (lo, _) = view["host_window"], view["window"]
    return [(lo + s - ta, lo + e - ta, a) for s, e, a in found]
