"""A statistic, in ms, of the idle gap between the end of one program of a kind and
the start of the next of that kind, where no program of another kind ran between."""
import numpy as np


def read(view, kind, percentile=50):
    lo, hi = view["window"]
    mods = [m for m in view["trace"]["devices"][0]["modules"]
            if m[3] != "other" and m[0] >= lo and m[1] <= hi]
    gaps = [b[0] - a[1] for a, b in zip(mods, mods[1:]) if a[3] == b[3] == kind]
    return float(np.percentile(gaps, percentile)) * 1e3 if gaps else None
