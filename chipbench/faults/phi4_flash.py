"""Planted faults for a Phi-4-mini-flash cell: the timed path broken underneath, so that
``correct`` has to come out false. Each breaks the program's model class in this
process, then the cell runs as ever:

``python3 -m chipbench.faults.phi4_flash --fault <name> --workload <cell> --seed <n> --seconds <s>``

- ``window_wide``: the window layers attend over twice the published window (1024 for
  512), which is the whole context until it passes that: rings of the whole 2048 would
  not fit the chip beside the weights;
- ``memory_stale``: the last Mamba layer reads out the state as it stood before this
  token, so the memory that the Gated Memory Units read is one step stale in the state;
- ``lam_zero``: ``lam`` forced to 0: the second softmax map of every pair is dropped;
- ``state_stuck``: a row's Mamba state is left as it was on every fifth position
  (``--every``)."""
import argparse
import json
import sys
import time

T_START = time.perf_counter()


def plant(name, every=5):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import phi4_flash as m
    from deeplearning4j_tpu.ops import ssm_pallas
    step = m.Phi4FlashLM.decode_step

    def state_stuck(self, params, tokens, positions, k, v, rk, rv, ssm, conv, tables, slots, **kw):
        old = ssm[:, slots]
        logits, k, v, rk, rv, new, conv = step(self, params, tokens, positions, k, v, rk, rv,
                                               ssm, conv, tables, slots, **kw)
        skip = (positions % every == 0)[None, :, None, None]
        return logits, k, v, rk, rv, new.at[:, slots].set(jnp.where(skip, old, new[:, slots])), conv

    if name == "window_wide":
        make = m.Phi4FlashConfig.from_published
        m.Phi4FlashConfig.from_published = staticmethod(
            lambda cfg, **kw: make(dict(cfg, sliding_window=2 * cfg["sliding_window"]), **kw))
    elif name == "lam_zero":
        out = m.Phi4FlashLM._diff_out
        m.Phi4FlashLM._diff_out = lambda self, p, layer, o: out(
            self, p, layer, o.at[..., 1, :].set(0.0))
    elif name == "memory_stale":
        update = ssm_pallas.selective_state_update

        def stale(state, layer, slots, x, dt, a, b, c):
            old = state[layer, slots]
            state, y = update(state, layer, slots, x, dt, a, b, c)
            if layer == state.shape[0] - 1:
                y = jnp.einsum("rnc,rn->rc", old, c.astype(old.dtype))
            return state, y
        ssm_pallas.selective_state_update = stale
    else:
        m.Phi4FlashLM.decode_step = state_stuck


FAULTS = ("window_wide", "memory_stale", "lam_zero", "state_stuck")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=FAULTS)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--every", type=int, default=5)
    a = ap.parse_args(argv)
    from chipbench import harness
    plant(a.fault, a.every)
    out = harness.measure(a.workload, a.seed, a.seconds, 0, t_start=T_START)
    sys.stdout.flush()
    print(json.dumps(dict(out, fault=a.fault)))


if __name__ == "__main__":
    main()
