"""1 - the union of device-operation intervals over the traced window, in %; the worst
device."""
from chipbench import trace as tr


def read(view):
    lo, hi = view["window"]
    return max(100.0 * (1.0 - tr.busy_seconds(d, lo, hi) / (hi - lo))
               for d in view["trace"]["devices"])
