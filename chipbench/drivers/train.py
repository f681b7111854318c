"""Window driver for training cells: steps through the program's own entry for the
whole window, at most one step ahead of the device."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness


def run(ctx):
    cfg, mix, seed = ctx["cfg"], ctx["mix"], ctx["seed"]
    builder, ref = harness.module("models", cfg["builder"]), harness.module("reference", cfg["reference"])
    prog = (ctx.get("build") or builder.build)(cfg, mix, seed, ctx["chips"])

    # Set-up drives the one object the window gets through its first steps, on
    # different batches, and keeps what the reference will be held against.
    n_proof = cfg["proof_steps"]
    before = prog.params_copy()
    losses, grad = [], None
    for i in range(n_proof):
        losses.append(prog.step(i))
        if i == 0:
            grad = prog.first_grad()
    names, change = prog.change_norms(before)
    del before
    got = {"loss": [float(x) for x in losses], "names": names, "grad": grad,
           "gnorm": np.asarray(ref.leaf_norms(grad)[1]), "dnorm": np.asarray(change)}
    prog.wait()

    def steps_for(seconds, i):
        t0, prev = time.perf_counter(), None
        while time.perf_counter() - t0 < seconds:
            h = prog.step(i)
            if prev is not None:
                prev.block_until_ready()
            prev, i = h, i + 1
        prog.wait()
        return i

    setup_s = time.perf_counter() - ctx["t_start"]
    t0 = time.perf_counter()
    i = n_proof
    if ctx["trace"]:
        i = steps_for(ctx["seconds"] / 3, i)
        with harness.traced(ctx):
            i = steps_for(min(cfg["trace_seconds"], ctx["seconds"] / 3), i)
    i = steps_for(ctx["seconds"] - (time.perf_counter() - t0), i)
    window = time.perf_counter() - t0
    steps = i - n_proof
    if prog.compiles_in_window():
        raise RuntimeError(f"{prog.compiles_in_window()} compiles inside the window")
    samples = prog.samples_per_step
    memory = harness.memory_peak()
    prog.close()
    del prog

    # The plain reference follows the same first steps from the same seed.
    xs, ys = ref.make_batches(cfg, seed, n_proof, samples)
    want = ref.first_steps(cfg, ref.make_params(cfg, seed), xs, ys)
    checks = compare(got, want, cfg["limits"], cfg["head_vertex"])
    ctx["want"] = want
    return {"attempted": steps, "failed": 0, "memory_peak_bytes": memory,
            "checks": checks, "numbers": {k: v[0] for k, v in checks.items()},
            "end_to_end": {"train_samples_per_s": steps * samples / window, "setup_s": setup_s},
            "records": {"samples_per_step": samples, "chips": ctx["chips"]}}


def compare(got, want, limits, head="output"):
    """Each number that the configuration gives a limit, beside it. A norm is compared leaf by leaf: the gap
    between the program's norm and the reference's over the reference's norm of that leaf
    or of the median leaf, whichever is larger; the number held to a limit is the median
    leaf's gap (the worst leaf's is the rounding of batch norm's cancelling sums, PERF.md
    section 4). Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of the change."""
    if list(got["names"]) != list(want["names"]):
        raise RuntimeError("the program's leaves are not the reference's")
    out = {}
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"])):
        out[f"loss{i + 1}_rel"] = abs(a - b) / abs(b)
    g, d = np.asarray(want["gnorm"], np.float64), np.asarray(want["dnorm"], np.float64)
    out["grad_norm_gap"] = float(np.median(np.abs(got["gnorm"] - g) / np.maximum(g, np.median(g))))
    moved = g >= 1e-3 * np.median(g)
    gap = np.abs(got["dnorm"] - d) / np.maximum(d, np.median(d[moved]))
    out["change_norm_gap"] = float(np.median(gap[moved]))
    # Zero-mean rounding cancels in a loss and in a norm, so neither tells bfloat16 from
    # float8; the first gradient's difference from the reference's does: over all leaves,
    # and over the classifier's alone, which only the forward pass feeds.
    sq = lambda t: sum(float(jnp.sum(jnp.square(a))) for a in jax.tree_util.tree_leaves(t))  # noqa: E731
    diff = jax.tree_util.tree_map(lambda a, b: a - b, got["grad"], want["grad"])
    out["grad_diff_rel"] = (sq(diff) / sq(want["grad"])) ** 0.5
    out["head_grad_diff_rel"] = (sq(diff[head]) / sq(want["grad"][head])) ** 0.5
    return {k: [v, limits[k]] for k, v in out.items() if k in limits}


def controls(ctx, ref, res):
    """What the control and the planted faults read against the reference: the reference
    in float8 put in the program's place, and the reference fed half of each batch."""
    cfg, seed, want = ctx["cfg"], ctx["seed"], ctx["want"]
    samples = res["records"]["samples_per_step"]
    xs, ys = ref.make_batches(cfg, seed, cfg["proof_steps"], samples)
    params = ref.make_params(cfg, seed)
    out = {}
    every = {k: 0.0 for k in ("loss1_rel", "loss2_rel", "loss3_rel", *cfg["limits"])}
    for name, kw in (("control_fp8", {"low": True}), ("fault_half_batch", {"rows": samples // 2})):
        if name not in ctx.get("only", name):
            continue
        got = ref.first_steps(cfg, params, xs, ys, **kw)
        out[name] = {k: v[0] for k, v in compare(got, want, every, cfg["head_vertex"]).items()}
    return out
