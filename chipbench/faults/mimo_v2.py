"""Planted faults for a MiMo-V2 cell: the timed path broken underneath, so that ``correct``
has to come out false. Each breaks the program's model class or its expert layer in this
process, then the cell runs as ever:

``python3 -m chipbench.faults.mimo_v2 --fault <name> --workload <cell> --seed <n> --seconds <s>``

- ``no_sink``: the window layers' learned sink left out of the softmax's denominator;
- ``window_wide``: the window layers attend over twice the published window (256 for 128);
- ``no_bias``: the selection bias left out of the top-8;
- ``no_renorm``: the chosen experts' scores used as gates without renormalising them;
- ``expert_dropped``: the rows routed to one held expert (the fourth) get nothing from it;
- ``rope_swapped``: the window layers' rotary base (``swa_rope_theta``) used in the full
  layers too."""
import argparse
import json
import sys
import time

T_START = time.perf_counter()

FAULTS = ("no_sink", "window_wide", "no_bias", "no_renorm", "expert_dropped", "rope_swapped")


def _with_params(change):
    """Every entry point of the model class sees ``change(params)``."""
    from deeplearning4j_tpu.models import mimo_v2 as m
    for name in ("prefill", "decode_step", "forward"):
        fn = getattr(m.MiMoV2LM, name)
        setattr(m.MiMoV2LM, name, (lambda fn: lambda self, params, *a, **kw: fn(
            self, change(params), *a, **kw))(fn))


def _layers(params, leaf, value):
    import jax.numpy as jnp
    return {k: (dict(p, **{leaf: jnp.full_like(p[leaf], value)}) if leaf in p else p)
            for k, p in params.items()}


def plant(name):
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import mimo_v2 as m
    from deeplearning4j_tpu.ops import moe
    make, route = m.MiMoV2Config.from_published, moe.route
    if name == "no_sink":
        _with_params(lambda params: _layers(params, "sink", -1e30))
    elif name == "no_bias":
        _with_params(lambda params: _layers(params, "bias", 0.0))
    elif name == "window_wide":
        m.MiMoV2Config.from_published = staticmethod(
            lambda cfg, **kw: make(dict(cfg, sliding_window=2 * cfg["sliding_window"]), **kw))
    elif name == "rope_swapped":
        m.MiMoV2Config.from_published = staticmethod(
            lambda cfg, **kw: make(dict(cfg, rope_theta=cfg["swa_rope_theta"]), **kw))
    elif name == "no_renorm":
        def raw(h, router, bias, top_k):
            import jax
            idx, _ = route(h, router, bias, top_k)
            s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                                       precision=jax.lax.Precision.HIGHEST))
            return idx, jnp.take_along_axis(s, idx, axis=-1)
        moe.route = raw
    elif name == "expert_dropped":
        layer = moe.held_expert_layer

        def dropped(h, router, bias, experts, first, count, **kw):
            def lossy(*a):
                idx, gates = route(*a)
                return idx, jnp.where(idx == first + 3, 0.0, gates)
            moe.route = lossy
            try:
                return layer(h, router, bias, experts, first, count, **kw)
            finally:
                moe.route = route
        moe.held_expert_layer = dropped
    else:
        raise ValueError(name)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=FAULTS)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    from chipbench import harness
    plant(a.fault)
    out = harness.measure(a.workload, a.seed, a.seconds, 0, t_start=T_START)
    sys.stdout.flush()
    print(json.dumps(dict(out, fault=a.fault)))


if __name__ == "__main__":
    main()
