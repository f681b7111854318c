"""A statistic, in ms, of the device durations of the programs of one kind in the
traced window; ``plus`` adds the duration of the program of that kind that follows."""
import numpy as np


def read(view, kind, percentile=50, plus=None):
    lo, hi = view["window"]
    dev = view["trace"]["devices"][0]
    mods = [m for m in dev["modules"] if m[3] != "other"]
    durs = []
    for i, m in enumerate(mods):
        if m[3] == kind and m[0] >= lo and m[1] <= hi:
            d = m[1] - m[0]
            if plus and i + 1 < len(mods) and mods[i + 1][3] == plus:
                d += mods[i + 1][1] - mods[i + 1][0]
            durs.append(d)
    return float(np.percentile(durs, percentile)) * 1e3 if durs else None
