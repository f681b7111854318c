"""Times the two lowerings of the served expert layer (``ops/moe.py``: ``dense`` |
``gmm``) on the chip, one expert layer at MiMo-V2.5's widths (4096 wide,
256 experts of which 16 are held, 8 a row, experts 2048 wide, bfloat16 weights), at a
decode step's rows and at the prompt buckets':

    python scripts/probe_moe_rungs.py [rows,rows,...]

Prints one JSON line a (rows, rung): the median of 20 runs in ms, the routed pairs, and the
largest difference from the ``gmm`` rung's result. A time from anything but a TPU is not
a device number: the script says which platform it ran on."""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops import moe

D, F, EXPERTS, HELD, TOP = 4096, 2048, 256, 16, 8


def main(argv):
    sizes = [int(n) for n in argv[1].split(",")] if len(argv) > 1 else [128, 512, 2048]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    router = jax.random.normal(ks[0], (D, EXPERTS)) * 0.02
    bias = jax.random.normal(ks[1], (EXPERTS,)) * 0.02
    experts = tuple((jax.random.normal(k, s) * 0.02).astype(bf) for k, s in
                    zip(ks[2:5], [(HELD, D, F), (HELD, D, F), (HELD, F, D)]))
    platform = jax.devices()[0].platform
    for n in sizes:
        h = jax.random.normal(jax.random.fold_in(ks[5], n), (n, D))
        want = None
        for rung in ("gmm", "dense"):
            if rung == "dense" and n > 2048:
                continue
            fn = jax.jit(lambda h, r=rung: moe.held_expert_layer(
                h, router, bias, experts, 0, HELD, top_k=TOP, rung=r))
            t0 = time.perf_counter()
            out, counts = jax.block_until_ready(fn(h))
            compile_s = time.perf_counter() - t0
            times = []
            for _ in range(20):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(h))
                times.append(time.perf_counter() - t0)
            want = out if want is None else want
            print(json.dumps({"platform": platform, "rows": n, "rung": rung,
                              "ms": round(1e3 * float(np.median(times)), 4),
                              "ms_min": round(1e3 * min(times), 4),
                              "pairs": int(counts[0]), "rows_max": int(counts[4]),
                              "compile_s": round(compile_s, 1),
                              "max_diff": float(jnp.max(jnp.abs(out - want)))}), flush=True)


if __name__ == "__main__":
    main(sys.argv)
