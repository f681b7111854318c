"""A share of a peak, in %: the operations or bytes that the algorithm needs for the work
the traced window did (counted from shapes by the configuration's ``counts`` module),
over a time from the trace times the chip's peak.

``work``: ``train`` (FLOPs of the whole steps in the window), ``generate`` (FLOPs of
every token that arrived in the window, prefill or decode), ``decode`` /
``paged_attention`` (FLOPs / attention bytes of the tokens of the whole decode steps in
the window).
``over``: ``window`` (its length, times the chips), ``programs`` (the device time of the
programs the work was done by) or ``ops`` (the device time of the operations inside
them whose short name matches ``ops``)."""
from chipbench import trace as tr

KIND = {"train": "train_step", "decode": "decode_step", "paged_attention": "decode_step"}


def _arrivals(view):
    """(profiler-clock arrival, prompt length, index in its request) of every token."""
    (ta, _), (lo, _) = view["host_window"], view["window"]
    return sorted((lo + t - ta, r["prompt"], k) for r in view["records"]["requests"]
                  for k, t in enumerate(r["times"]))


def read(view, work, over, ops=None):
    lo, hi = view["window"]
    cfg, counts, dev = view["cfg"], view["counts"], view["trace"]["devices"][0]
    if work == "generate":
        mods = None
        amount = sum(counts.prefill_flops(cfg, p) if k == 0 else counts.decode_flops(cfg, p + k)
                     for t, p, k in _arrivals(view) if lo <= t <= hi)
    else:
        mods = tr.modules_of(dev, KIND[work], lo, hi)
        if len(mods) < 2:
            return None
        if work == "train":
            amount = len(mods) * view["records"]["samples_per_step"] * counts.train_flops(cfg)
        else:
            # A step's tokens reach the client just after its program ends: take the
            # tokens of every whole step of the window but the first.
            slack = 0.4 * min(m[1] - m[0] for m in mods)
            mine = [(p, k) for t, p, k in _arrivals(view)
                    if k > 0 and mods[0][1] + slack < t <= mods[-1][1] + slack]
            mods = mods[1:]
            if work == "decode":
                amount = sum(counts.decode_flops(cfg, p + k) for p, k in mine)
            else:
                by_len = [p + k for p, k in mine]
                amount = counts.paged_attention_bytes(cfg, by_len)
    if not amount:
        return None
    if over == "window":
        seconds = (hi - lo) * view["chips"]
    elif over == "programs":
        seconds = sum(m[1] - m[0] for m in mods)
    else:
        seconds = tr.op_seconds(dev, ops, mods[0][0], mods[-1][1], KIND[work])
    if not seconds:
        return None
    peak = view["peaks"]["hbm_bytes_per_s" if work == "paged_attention" else "bf16_flops"]
    return 100.0 * amount / (seconds * peak)
