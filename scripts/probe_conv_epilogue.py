#!/usr/bin/env python
"""Whole programs with the ``conv_epilogue`` family on and off: the chip reading that the
family's auto rung cites (``ops/conv_pallas.py``, PERF.md section 6, PR 33). On the chip,
through the chip tool: ``python scripts/probe_conv_epilogue.py [modes]`` with ``modes`` a
comma list of ``auto`` (gate unset), ``0`` (killed) and ``1`` (forced), default all three.

Three programs of the zoo, each built afresh under each mode and timed over whole calls that
end in ``block_until_ready``: ResNet-50 forward-only at b256 in bfloat16 (inference-mode BN
is an epilogue site, 53 of them), and one ``fit`` step of VGG16 at b32 and of AlexNet at
b256 (every conv carries a bias and a ReLU). Each line says which rung decided (and, for the
forward program, how many Mosaic calls it holds compiled); the last line is JSON. Nothing here is a
benchmark's number."""
import json
import sys
import time

import numpy as np


def gate(mode):
    from deeplearning4j_tpu.common.environment import Environment
    extra = Environment.get().extra
    if mode == "auto":
        extra.pop("fused_conv", None)
    else:
        extra["fused_conv"] = mode


def timed(call, sync, warm, calls):
    t0 = time.perf_counter()
    for _ in range(warm):
        call()
    sync()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(calls):
        call()
    sync()
    return first, (time.perf_counter() - t0) / calls


def resnet50_output(batch=256, calls=20):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo import ResNet50
    net = ResNet50(num_classes=1000, height=224, width=224,
                   compute_dtype="bfloat16").init()
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, 224, 224, 3), jnp.float32)
    out = net.conf.network_outputs[0]
    fwd = jax.jit(lambda p, s, a: net._forward(p, s, [a], training=False, rng=None,
                                               want_logits=False)[0][out])
    hold = []

    def call():
        hold[:] = [fwd(net.params, net.states, x)]
    first, per = timed(call, lambda: jax.block_until_ready(hold), 2, calls)
    text = fwd.lower(net.params, net.states, x).compile().as_text()
    return batch, first, per, text.count("tpu_custom_call")


def zoo_fit(name, batch, calls=10):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models import zoo
    net = getattr(zoo, name)(num_classes=1000, height=224, width=224).init()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, 224, 224, 3).astype(np.float32))
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, batch)])
    ds = DataSet(x, y)
    first, per = timed(lambda: net.fit(ds), lambda: jax.block_until_ready(net.params),
                       2, calls)
    return batch, first, per, None


PROGRAMS = {
    "resnet50.output.b256": resnet50_output,
    "vgg16.fit.b32": lambda: zoo_fit("VGG16", 32),
    "alexnet.fit.b256": lambda: zoo_fit("AlexNet", 256),
}


def main():
    import gc

    import jax

    import deeplearning4j_tpu  # noqa: F401
    from deeplearning4j_tpu.ops import kernel_select
    modes = (sys.argv[1] if len(sys.argv) > 1 else "auto,0,1").split(",")
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind}, "runs": []}
    for name, program in PROGRAMS.items():
        for mode in modes:
            gate(mode)
            before = kernel_select.decisions("conv_epilogue")
            batch, first, per, mosaic = program()
            now = kernel_select.decisions("conv_epilogue")
            took = {d: now[d] - before[d] for d in now if now[d] != before[d]}
            run = {"program": name, "fused_conv": mode, "decisions": took,
                   "mosaic_calls": mosaic, "first_calls_s": first, "call_ms": per * 1e3,
                   "samples_per_s": batch / per}
            out["runs"].append(run)
            print(run, flush=True)
            gc.collect()
    gate("auto")
    print(json.dumps(out))


if __name__ == "__main__":
    sys.path.insert(0, ".")
    main()
