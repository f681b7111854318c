"""A percentile, in ms, of the durations of one named span of the program inside the
traced window."""
import numpy as np

from chipbench.readers import ring


def read(view, span, percentile=50, required=False):
    found = ring.inside(view, span, view["host_window"], required)
    if not found:
        return None
    return float(np.percentile([e - s for s, e, _ in found], percentile)) * 1e3
