"""A prefill scan kernel's share of the HBM roof, in %: what the scans of the prompts
admitted in the traced window had to read and write (``counts.selective_scan_bytes`` of
each ``generate.prefill`` span's ``tokens``, the prompt without its bucket's padding),
over the device time of the operations in the window whose short name matches ``ops``,
inside the programs of kind ``admit``, times the chip's bandwidth. Nothing where the
configuration has no such count, the program no such operation or spans, or no prompt
was admitted in the window."""
from chipbench import trace as tr
from chipbench.readers import ring


def read(view, ops):
    counts = view["counts"]
    if not hasattr(counts, "selective_scan_bytes"):
        return None
    found = ring.inside(view, "generate.prefill", view["host_window"])
    if not found:
        return None
    lo, hi = view["window"]
    seconds = tr.op_seconds(view["trace"]["devices"][0], ops, lo, hi, "admit")
    if not seconds:
        return None
    amount = sum(counts.selective_scan_bytes(view["cfg"], a["tokens"]) for _, _, a in found)
    return 100.0 * amount / (seconds * view["peaks"]["hbm_bytes_per_s"])
