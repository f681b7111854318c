"""Planted faults for a Falcon-H1 cell: the timed path broken underneath, so that
``correct`` has to come out false. Each breaks the program's model class in this
process, then the cell runs as ever:

``python3 -m chipbench.faults.falcon_h1 --fault <name> --workload <cell> --seed <n> --seconds <s>``

- ``state_stuck``: a row's recurrent state is left as it was on every fifth position
  (``--every``);
- ``tail_stuck``: the convolution tail is never advanced after the prompt;
- ``no_key_multiplier``: ``key_multiplier`` is left out of the attention's keys."""
import argparse
import json
import sys
import time

T_START = time.perf_counter()


def plant(name, every=5):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import falcon_h1 as m
    step = m.FalconH1LM.decode_step

    def state_stuck(self, params, tokens, positions, k, v, ssm, conv, tables, slots, **kw):
        old = ssm[:, slots]
        logits, k, v, new, conv = step(self, params, tokens, positions, k, v, ssm, conv,
                                       tables, slots, **kw)
        skip = (positions % every == 0)[None, :, None, None, None]
        return logits, k, v, new.at[:, slots].set(jnp.where(skip, old, new[:, slots])), conv

    def tail_stuck(self, params, tokens, positions, k, v, ssm, conv, tables, slots, **kw):
        old = conv[:, slots]
        logits, k, v, ssm, new = step(self, params, tokens, positions, k, v, ssm, conv,
                                      tables, slots, **kw)
        return logits, k, v, ssm, new.at[:, slots].set(old)

    if name == "no_key_multiplier":
        make = m.FalconH1Config.from_published
        m.FalconH1Config.from_published = staticmethod(
            lambda cfg, **kw: make(dict(cfg, key_multiplier=1.0), **kw))
    else:
        m.FalconH1LM.decode_step = {"state_stuck": state_stuck, "tail_stuck": tail_stuck}[name]


FAULTS = ("state_stuck", "tail_stuck", "no_key_multiplier")


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
