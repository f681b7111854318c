"""A state-update kernel's share of the HBM roof, in %: the recurrent state that the
live rows of the whole decode steps of the traced window had to read and write
(``counts.ssm_state_bytes``: one row a token that a step served), over the device time
of the operations inside those steps whose short name matches ``ops``, times the chip's
bandwidth. Nothing where the configuration has no recurrent state, or the program no
such operation."""
from chipbench import trace as tr
from chipbench.readers import share


def read(view, ops):
    counts = view["counts"]
    if not hasattr(counts, "ssm_state_bytes"):
        return None
    lo, hi = view["window"]
    dev = view["trace"]["devices"][0]
    mods = tr.modules_of(dev, "decode_step", lo, hi)
    if len(mods) < 2:
        return None
    # as share.read: the tokens of every whole step of the window but the first
    slack = 0.4 * min(m[1] - m[0] for m in mods)
    rows = sum(1 for t, _, k in share._arrivals(view)
               if k > 0 and mods[0][1] + slack < t <= mods[-1][1] + slack)
    seconds = tr.op_seconds(dev, ops, mods[1][0], mods[-1][1], "decode_step")
    if not rows or not seconds:
        return None
    return 100.0 * counts.ssm_state_bytes(view["cfg"], rows) / (
        seconds * view["peaks"]["hbm_bytes_per_s"])
