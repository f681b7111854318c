"""The grouped expert products' share of their roof, in %: what the routed rows of the traced
window needed (``counts.expert_bytes`` of the hit experts' weights and the rows in and out,
``counts.expert_flops`` of the rows: the program's own ``moe_experts_hit`` and ``moe_rows`` on
the spans that carry a program's routing counts), at the nearer of the HBM roof and the
bf16 peak, over the device time of the operations whose short name matches ``ops`` inside
the programs of one kind.

``span`` ``generate.emit`` (a decode step's counts, pulled with its ids) goes with ``kind``
``decode_step``; ``generate.prefill`` with ``admit``. Nothing where the configuration has no
such counts, the program no such spans or attributes (a program from before the expert
layer), or the lowering that ran no operation the pattern names."""
from chipbench import trace as tr
from chipbench.readers import ring


def read(view, ops, span, kind):
    counts = view["counts"]
    if not hasattr(counts, "expert_bytes"):
        return None
    found = [a for _, _, a in ring.inside(view, span, view["host_window"]) or ()
             if "moe_rows" in a]
    if not found:
        return None
    lo, hi = view["window"]
    seconds = tr.op_seconds(view["trace"]["devices"][0], ops, lo, hi, kind)
    if not seconds:
        return None
    cfg, peaks = view["cfg"], view["peaks"]
    least = sum(max(counts.expert_bytes(cfg, a["moe_experts_hit"], a["moe_rows"]) / peaks["hbm_bytes_per_s"],
                    counts.expert_flops(cfg, a["moe_rows"]) / peaks["bf16_flops"]) for a in found)
    return 100.0 * least / seconds
