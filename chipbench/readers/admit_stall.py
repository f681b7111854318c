"""What a row that was decoding waits between two tokens when an admission falls between
them, from the program's own ``generate.stall`` records (one an admission episode: from the
last emit before the episode's first prefill to the first emit after it). ``stat`` ``ms``
is a percentile of their durations; ``gap_share`` is the sum of their ``rows`` (the
inter-token gaps that crossed an episode) over the ``tokens`` of every ``generate.emit``
(all inter-token gaps: a request's first token comes from its prefill), in %. Both over
the whole timed window, as ``span_attr`` reads: a traced seventh of it holds a dozen
episodes in some cells, too few for a tail. The window's sums, with the ``prefills`` and
``prompt_tokens`` the episodes held, go to standard error. A program that writes no
``generate.stall`` gives nothing."""
import sys

import numpy as np

from chipbench.readers import ring


def window(view):
    """``([seconds, one an episode], rows that waited, tokens emitted)`` of the timed
    window, or None."""
    if "_admit_stall" not in view:
        span = (view["records"]["t0"], view["records"]["t_end"])
        stalls = ring.inside(view, "generate.stall", span)
        view["_admit_stall"] = None
        if stalls:
            args = [a for _, _, a in stalls]
            rows = sum(a["rows"] for a in args)
            tokens = sum(a["tokens"] for _, _, a in ring.inside(view, "generate.emit", span))
            print(f"admit_stall: {len(stalls)} episodes of {sum(a['prefills'] for a in args)} prefills, "
                  f"{sum(a['prompt_tokens'] for a in args)} prompt tokens; {rows} of {tokens} "
                  "inter-token gaps crossed one", file=sys.stderr)
            view["_admit_stall"] = ([e - s for s, e, _ in stalls], rows, tokens)
    return view["_admit_stall"]


def read(view, stat, percentile=50):
    found = window(view)
    if found is None:
        return None
    seconds, rows, tokens = found
    if stat == "ms":
        return float(np.percentile(seconds, percentile)) * 1e3
    return 100.0 * rows / tokens if tokens else None
