"""How often the router's top-k differs between the served program and the benchmark's plain
reference, on the chip: the near ties at rank ``top_k`` that flip under the stream's rounding
(PERF.md section 4, PR 34). Prompts of one length drawn from the seed go through the
program's prefill and through the reference's forward pass (float32 at ``highest``), and
through the reference with bfloat16 activations (the control of ``correct``); every expert
layer's chosen experts are read out of both and compared row by row:

    python scripts/probe_moe_flips.py [--workload mimo-v2.5.agent-closed] [--seed n]
                                      [--prompts 4] [--tokens 2048]

Prints one JSON line: of the rows (a position in an expert layer), the share whose **held
set** (the chosen experts this chip holds) differs from the reference's, the share whose
chosen set differs at all, and the share of rows that have a held expert; for the program
and for the control. The shares are counts, but of a product at the TPU's default precision:
run it through the chip tool."""
import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np


def _tap(module, stash):
    """``module.route`` also leaves the experts it chose in ``stash`` (tracers of the trace
    that called it: the caller returns them)."""
    route = module.route

    def tapped(*a, **kw):
        idx, gates = route(*a, **kw)
        stash.append(idx)
        return idx, gates
    module.route = tapped
    return route


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mimo-v2.5.agent-closed")
    ap.add_argument("--seed", type=int, default=3400000061)
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=2048)
    a = ap.parse_args()
    from chipbench import harness
    from chipbench.models.mimo_v2 import program_layout
    from chipbench.reference import mimo_v2 as ref
    from deeplearning4j_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2LM
    from deeplearning4j_tpu.ops import moe
    _, _, cfg, _ = harness.cell_of(a.workload)
    weights = ref.make_params(cfg, a.seed)
    model = MiMoV2LM(MiMoV2Config.from_published(cfg, max_len=cfg["max_len"],
                                                 eos_id=cfg["vocab_size"]))
    params = program_layout(weights)
    first, held, t = cfg.get("experts_first", 0), cfg["n_routed_experts"], a.tokens

    mine, theirs = [], []
    _tap(moe, mine)
    _tap(ref, theirs)

    @jax.jit
    def program(params, tokens):
        del mine[:]
        model.prefill(params, tokens[None], jnp.asarray([t], jnp.int32))
        return jnp.stack(mine)                      # [layers, t, top]

    def reference(act, precision):
        @jax.jit
        def fn(weights, tokens):
            del theirs[:]
            with jax.default_matmul_precision(precision):
                ref.hidden(cfg, weights, tokens, act)
            return jnp.stack(theirs)
        return fn

    exact = reference(jnp.float32, "highest")
    control = reference(jnp.bfloat16, "default")

    def sets(idx):                                  # [layers, t, top] -> [layers, t, experts]
        return np.asarray(jnp.any(idx[..., None] == jnp.arange(cfg["router_experts"]), axis=-2))

    tally = {k: np.zeros(3) for k in ("program", "control")}
    rows = with_held = 0
    rng = np.random.default_rng(a.seed)
    for _ in range(a.prompts):
        tokens = jnp.asarray(rng.integers(0, cfg["vocab_size"], (t,)), jnp.int32)
        want = sets(exact(weights, tokens))
        rows += want.shape[0] * want.shape[1]
        with_held += int(want[..., first:first + held].any(-1).sum())
        for name, got in (("program", program(params, tokens)), ("control", control(weights, tokens))):
            got = sets(got)
            differ = (got != want)
            tally[name] += [differ[..., first:first + held].any(-1).sum(), differ.any(-1).sum(),
                            (differ.any(-1) & ~differ[..., first:first + held].any(-1)).sum()]
    out = {"platform": jax.devices()[0].platform, "seed": a.seed, "prompts": a.prompts, "tokens": t,
           "expert_layers": int(sum(cfg["moe_layer_freq"][:cfg["num_hidden_layers"]])), "rows": rows,
           "rows_with_a_held_expert_share": with_held / rows}
    for name, (held_differs, any_differs, elsewhere) in tally.items():
        out[name] = {"held_set_differs_share": held_differs / rows, "chosen_set_differs_share": any_differs / rows,
                     "differs_elsewhere_only_share": elsewhere / rows}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
