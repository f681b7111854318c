"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once on a TPU, through
the entry points a user calls, at full width with seeded random
weights, and checks what comes out by the repo's own means:

  native    the host library is built by ``make`` and loaded;
  train     ResNet-50 (224 px, 1000 classes, bf16, batch 128):
            ``fit`` x3, a burst of ``fit``, ``fit_steps`` x2 — finite
            loss after every step; the BN families take XLA's
            lowering by the auto rung and the compiled step holds no
            Mosaic call; an identically seeded net whose ladder is
            forced holds the kernels and agrees with step 1;
  serve     the same network behind ``ModelRegistry`` +
            ``InferenceServer`` over loopback: concurrent ``:predict``
            requests agree with ``net.output``, ``/readyz`` is 200,
            nothing retraces after warm-up;
  generate  ``DecoderLM`` at the GPT-2-small shape behind
            ``:generate``: four concurrent streams to their end, the
            paged kernel in the compiled decode step, and one decode
            step's logits against the dense-gather lowering;
  hybrid    the Falcon-H1 block (Mamba-2 beside grouped-query
            attention): the paged kernel with five query heads a KV
            head and ``pallas.ssm_state_update`` at the published head
            shapes, each against its ``jax.numpy`` form, then one
            prefill and eight decode steps of ``FalconH1LM`` through
            K/V blocks and a state slot against the plain reference;
  kernels   every TPU-default Pallas kernel the model phases do not
            reach, un-interpreted, against its dense lowering;
  four-chip (``len(jax.devices()) >= 4``) ResNet-50 through
            ``ParallelWrapper`` on four devices, then
            ``__graft_entry__.dryrun_multichip(4)``.

It has no CPU mode: without a TPU it names the platform it found and
exits 2 before compiling anything. A failed check raises — no phase is
skipped, nothing is caught and carried past. Wall times are
orientation, not a benchmark. The last stdout line is the result:
``{"ok": true, "device": {"platform", "kind", "count"}}``.

The phases are importable functions that take their sizes, so
``tests/test_chip_smoke.py`` runs them tiny on the CPU with the
kernels in interpret mode.
"""
from __future__ import annotations

import gc
import http.client
import io
import json
import sys
import threading
import time
import zlib
from importlib import metadata

import numpy as np

FAMILIES = ("conv_epilogue", "bn_fwd", "bn_bwd", "attention",
            "paged_attention", "ssm_state")
#: the Mosaic entry point in compiled HLO / lowered StableHLO text
MOSAIC_TARGET = "tpu_custom_call"

# Tolerances. Every comparison is the relative Frobenius error
# ||a - b|| / ||b|| of a kernel against its dense lowering ON THE SAME
# DEVICE; the two differ in rounding order only.
#: bf16 operands (8 mantissa bits, ~4e-3 per rounding) through one
#: kernel, or f32 operands against an XLA einsum that the TPU's
#: default matmul precision rounds to bf16 passes
KERNEL_REL_TOL = 2e-2
#: a whole bf16 ResNet-50 step: 53 BN layers forward and backward in a
#: different rounding order, the loss itself a bf16 scalar
STEP_REL_TOL = 5e-2


class SmokeFailure(AssertionError):
    """A check did not hold. Raised, never caught: the run ends here."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def decisions_now() -> dict:
    from deeplearning4j_tpu.ops import kernel_select
    return {k: kernel_select.decisions(k) for k in FAMILIES}


def decisions_since(before: dict) -> dict:
    """Ladder decisions made since ``before``, zero rungs dropped."""
    now = decisions_now()
    return {k: {d: now[k][d] - before[k][d] for d in now[k]
                if now[k][d] != before[k][d]} for k in FAMILIES}


def mosaic_calls(lowered) -> tuple:
    """(Mosaic custom calls in the compiled HLO of a lowered program,
    seconds the compile took). With the persistent cache on this is a
    load, not a second compile."""
    t0 = time.perf_counter()
    text = lowered.compile().as_text()
    return text.count(MOSAIC_TARGET), time.perf_counter() - t0


def expected_mosaic(n_fused_calls: int) -> int:
    """Mosaic calls a program must hold for ``n_fused_calls`` traced
    ``pallas_call`` sites: all of them on the chip, none in interpret
    mode (there the kernels lower to plain HLO)."""
    from deeplearning4j_tpu.ops import kernel_select
    return 0 if kernel_select.interpret_mode() else n_fused_calls


class ladder:
    """``Environment.extra`` gate overrides for a with-block (the
    kernel-select ladder's force/kill rung), restored on exit."""

    def __init__(self, **extra):
        self.extra = extra

    def __enter__(self):
        from deeplearning4j_tpu.common.environment import Environment
        env = Environment.get().extra
        self.saved = {k: env.get(k) for k in self.extra}
        env.update(self.extra)

    def __exit__(self, *exc):
        from deeplearning4j_tpu.common.environment import Environment
        env = Environment.get().extra
        for k, v in self.saved.items():
            if v is None:
                env.pop(k, None)
            else:
                env[k] = v
        return False


# ----------------------------------------------------------------------
def require_tpu() -> dict:
    """First action: what did jax find? Anything but a TPU ends the
    run with code 2 before a single program is compiled."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: jax found platform={dev['platform']!r} "
              f"({dev['kind']}, {dev['count']} device(s)), not a TPU "
              f"— this script has no CPU mode", file=sys.stderr)
        sys.exit(2)
    return dev


def cache_entries() -> int:
    """Files in the compile cache directory in force (0: none yet)."""
    import os

    import jax
    d = jax.config.jax_compilation_cache_dir
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


def print_header(dev: dict) -> None:
    import jax
    import jaxlib
    say("device", f"platform={dev['platform']} kind={dev['kind']!r} "
                  f"count={dev['count']}")
    say("device", f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
                  f"libtpu={metadata.version('libtpu')} "
                  f"python={sys.version.split()[0]}")
    say("device", "compile cache directory in force: "
                  f"{jax.config.jax_compilation_cache_dir} "
                  f"({cache_entries()} entries at start)")


# ----------------------------------------------------------------------
def phase_native() -> dict:
    """The host library is what ``make`` builds from this checkout —
    a Python fallback here is a failure."""
    from deeplearning4j_tpu import native
    ok = native.ensure_built()
    state, detail = native.status()
    say("native", f"{state}: {detail}")
    require(ok and state in ("built", "loaded"),
            f"native library is {state}: {detail}")
    require(native.crc32(b"chip_smoke") == zlib.crc32(b"chip_smoke"),
            "native crc32 disagrees with zlib")
    return {"ok": True, "state": state}


# ----------------------------------------------------------------------
def _resnet(hw, classes, stages):
    from deeplearning4j_tpu.models.zoo import ResNet50
    kw = {"STAGES": tuple(stages)} if stages else {}
    return ResNet50(num_classes=classes, height=hw, width=hw,
                    compute_dtype="bfloat16", **kw).init()


def _image_batch(batch, hw, classes, seed=0):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.RandomState(seed)
    x = rng.randn(batch, hw, hw, 3).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.randint(0, classes, batch)]
    return x, y, DataSet(jax.device_put(jnp.asarray(x)),
                         jax.device_put(jnp.asarray(y)))


def _step1(net, ds):
    """One ``fit`` from fresh params: (loss, global norm of the
    parameter delta, wall seconds)."""
    import jax
    import jax.numpy as jnp
    before = jax.tree_util.tree_map(jnp.copy, net.params)
    t0 = time.perf_counter()
    net.fit(ds)
    loss = float(net.score())
    wall = time.perf_counter() - t0
    sq = jax.jit(lambda a, b: sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)
                           - y.astype(jnp.float32)))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))))
    return loss, float(np.sqrt(float(sq(net.params, before)))), wall


def _train_step_mosaic_calls(net):
    """Mosaic calls in the compiled train step, lowered at the last
    fitted shapes."""
    return mosaic_calls(net.lower_train_step())


def _bn_sites(rung, fused):
    """The BN sites of the step traced last, every one required to
    have decided both families by ``rung``."""
    from deeplearning4j_tpu.common import layerprof
    sites = layerprof.kernel_decisions()
    bn_sites = [s for s, per in sites.items() if "bn_bwd" in per]
    require(bn_sites, "no BN site reached the ladder")
    for s in bn_sites:
        for fam in ("bn_fwd", "bn_bwd"):
            d = sites[s].get(fam)
            require(d is not None and d["fused"] == fused
                    and d["decision"] == rung,
                    f"{s}: {fam} is {d}, expected {rung}")
    return bn_sites


def phase_train(*, batch=128, hw=224, classes=1000, stages=None,
                fit_calls=3, burst=10, steps=10) -> dict:
    """Train ResNet-50 through ``fit`` and ``fit_steps`` with the
    ladder on auto; returns the trained net under ``"net"`` for the
    serve phase.

    Auto is XLA's lowering at every BN site, on the chip as on the
    CPU (``auto_dense``: the whole step is 2.58 times faster without
    the kernels, PERF.md section 6, PR 33), and the compiled step
    holds no Mosaic call. The kernels stay reachable by the force
    rung: the same step from the same seed with both BN families
    forced must take them at every site, hold their Mosaic calls (on
    the chip; interpret mode on the CPU) and agree with the auto
    step."""
    import jax
    from deeplearning4j_tpu.common import layerprof

    _, _, ds = _image_batch(batch, hw, classes)

    layerprof.reset_decisions()
    before = decisions_now()
    net = _resnet(hw, classes, stages)
    loss1, dnorm1, first = _step1(net, ds)
    traced = decisions_since(before)
    say("train", f"fit #1 (compile + step): {first:.1f}s "
                 f"loss={loss1:.4f} |dparams|={dnorm1:.4f}")
    require(np.isfinite(loss1), f"step 1 loss {loss1}")
    say("train", f"ladder decisions in the step's trace: {traced}")

    # every BN site took XLA's lowering, by the auto rung
    bn_sites = _bn_sites("auto_dense", False)
    require(set(traced["bn_fwd"]) == {"auto_dense"}
            and set(traced["bn_bwd"]) == {"auto_dense"},
            f"BN families not uniformly auto_dense: {traced}")
    # ResNet-50's convs carry no bias and identity activation
    require(set(traced["conv_epilogue"]) <= {"structural"},
            f"conv_epilogue: {traced['conv_epilogue']}")

    losses = [loss1]
    later = []
    for _ in range(fit_calls - 1):
        t0 = time.perf_counter()
        net.fit(ds)
        losses.append(float(net.score()))
        later.append(time.perf_counter() - t0)
        require(np.isfinite(losses[-1]),
                f"loss {losses[-1]} at step {len(losses)}")
    say("train", "fit later calls (each synced on its loss): "
                 + ", ".join(f"{t:.3f}s" for t in later)
                 + " losses=" + ", ".join(f"{v:.4f}" for v in losses))

    # what a fit() dispatch costs: a burst of un-synced calls
    t0 = time.perf_counter()
    for _ in range(burst):
        net.fit(ds)
    jax.block_until_ready(net.params)
    t_ready = time.perf_counter()
    loss_b = float(net.score())
    t_read = time.perf_counter()
    require(np.isfinite(loss_b), f"loss {loss_b} after the burst")
    say("train", f"{burst} fit() calls, one sync: "
                 f"{t_ready - t0:.3f}s to block_until_ready, "
                 f"+{t_read - t_ready:.4f}s to read the loss on "
                 f"the host (loss={loss_b:.4f})")

    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        net.fit_steps(ds, steps)
        jax.block_until_ready(net.params)
        t_ready = time.perf_counter()
        loss_s = float(net.score())
        walls.append((t_ready - t0, time.perf_counter() - t_ready))
        require(np.isfinite(loss_s), f"fit_steps loss {loss_s}")
    say("train", f"fit_steps({steps}) first call (compile + run): "
                 f"{walls[0][0]:.1f}s; later call: "
                 f"{walls[1][0]:.3f}s to block_until_ready, "
                 f"+{walls[1][1]:.4f}s to read the loss "
                 f"(loss={loss_s:.4f})")

    n_auto, t_hlo = _train_step_mosaic_calls(net)
    say("train", f"compiled train step on auto: {n_auto} Mosaic custom "
                 f"calls for {len(bn_sites)} BN sites (lower+compile "
                 f"{t_hlo:.1f}s)")
    require(n_auto == 0, f"{n_auto} Mosaic calls in the auto step")

    # the same step with both BN families forced, identical seed
    with ladder(fused_conv="1", fused_bn_bwd="1"):
        layerprof.reset_decisions()
        before = decisions_now()
        forced_net = _resnet(hw, classes, stages)
        loss_k, dnorm_k, wall_k = _step1(forced_net, ds)
        forced = decisions_since(before)
        forced_sites = _bn_sites("forced", True)
        n_mosaic, _ = _train_step_mosaic_calls(forced_net)
    del forced_net
    require(set(forced["bn_fwd"]) == {"forced"}
            and set(forced["bn_bwd"]) == {"forced"}
            and len(forced_sites) == len(bn_sites),
            f"ladder force did not take: {forced}")
    # bn_fwd = statistics + normalize, bn_bwd = sums + dx
    want = expected_mosaic(4 * len(forced_sites))
    say("train", f"forced ladder, same seed ({wall_k:.1f}s): "
                 f"{n_mosaic} Mosaic custom calls for "
                 f"{len(forced_sites)} BN sites x 4 kernels (expected "
                 f"{want})")
    require(n_mosaic == want,
            f"{n_mosaic} Mosaic calls in the forced step, {want} "
            f"kernel sites counted as fused")
    e_loss = abs(loss_k - loss1) / max(abs(loss1), 1e-30)
    e_norm = abs(dnorm_k - dnorm1) / max(dnorm1, 1e-30)
    say("train", f"loss={loss_k:.4f} |dparams|={dnorm_k:.4f}; "
                 f"kernels vs auto (dense): loss rel {e_loss:.2e}, "
                 f"|dparams| rel {e_norm:.2e} (tol {STEP_REL_TOL})")
    require(e_loss <= STEP_REL_TOL and e_norm <= STEP_REL_TOL,
            f"kernel step disagrees with dense step: loss {loss_k} vs "
            f"{loss1}, |dparams| {dnorm_k} vs {dnorm1}")
    return {"ok": True, "net": net, "first_call_s": first,
            "bn_sites": len(bn_sites), "mosaic_calls": n_mosaic}


# ----------------------------------------------------------------------
def _npy_bytes(a) -> bytes:
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(a))
    return buf.getvalue()


def _in_threads(fn, n):
    """Run ``fn(i)`` for i < n on client threads; their results in
    order. A client's exception is re-raised here."""
    out, errs = [None] * n, [None] * n

    def run(i):
        try:
            out[i] = fn(i)
        except Exception as e:          # re-raised below, not dropped
            errs[i] = e

    ts = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(600)
    for t, e in zip(ts, errs):
        require(not t.is_alive(), "a client thread did not finish")
        if e is not None:
            raise e
    return out


def phase_serve(net, *, hw=224, n_clients=6, rows=(1, 2, 3, 4),
                buckets=(8, 32), force_kernels=False) -> dict:
    """``net`` behind the registry and the HTTP server, raw ``.npy``
    requests over loopback from client threads in this process."""
    import jax

    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.serving.server import InferenceServer

    gates = {"fused_conv": "1"} if force_kernels else {}
    n_dev = len(jax.devices())
    rng = np.random.RandomState(7)
    xs = [rng.randn(rows[i % len(rows)], hw, hw, 3).astype(np.float32)
          for i in range(n_clients)]

    with ladder(**gates):
        before = decisions_now()
        reg = ModelRegistry(default_buckets=buckets)
        srv = None
        try:
            t0 = time.perf_counter()
            ver = reg.register("resnet50", net,
                               warmup_shape=(hw, hw, 3))
            warm = time.perf_counter() - t0
            traced = decisions_since(before)
            say("serve", f"register + warm-up of buckets "
                         f"{ver.batcher.buckets}: {warm:.1f}s; ladder: "
                         f"{traced['conv_epilogue']}")
            srv = InferenceServer(reg).start(port=0)

            def get(path):
                c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                               timeout=60)
                try:
                    c.request("GET", path)
                    r = c.getresponse()
                    r.read()
                    return r.status
                finally:
                    c.close()

            def predict(i):
                c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                               timeout=300)
                try:
                    c.request(
                        "POST", "/v1/models/resnet50:predict",
                        body=_npy_bytes(xs[i]),
                        headers={"Content-Type":
                                 "application/octet-stream"})
                    r = c.getresponse()
                    body = r.read()
                    require(r.status == 200,
                            f"predict -> {r.status}: {body[:200]!r}")
                    return np.load(io.BytesIO(body))
                finally:
                    c.close()

            require(get("/readyz") == 200, "/readyz is not 200")
            t0 = time.perf_counter()
            outs = _in_threads(predict, n_clients)
            wall = time.perf_counter() - t0
            retraces = reg.retraces_since_warmup("resnet50")
            say("serve", f"{n_clients} concurrent :predict requests "
                         f"(rows {[x.shape[0] for x in xs]}): "
                         f"{wall:.3f}s, /readyz 200, retraces since "
                         f"warm-up: {retraces}")
            require(retraces == 0, f"{retraces} retraces after warm-up")
            # one direct call over every request's rows (net.output
            # runs op by op; each new batch shape compiles them all)
            ref_all = np.asarray(net.output(np.concatenate(xs)),
                                 np.float32)
            worst, at = 0.0, 0
            for x, out in zip(xs, outs):
                ref = ref_all[at:at + x.shape[0]]
                at += x.shape[0]
                require(out.shape == ref.shape,
                        f"shape {out.shape} vs {ref.shape}")
                require(np.all(np.isfinite(out)), "non-finite output")
                worst = max(worst, rel_err(out, ref))
            # a whole bf16 network in two differently shaped programs
            # (padded bucket, possibly sharded, vs op by op)
            say("serve", f"served vs net.output(x): worst rel error "
                         f"{worst:.2e} (tol {STEP_REL_TOL})")
            require(worst <= STEP_REL_TOL,
                    f"served outputs differ from net.output: {worst}")

            # inference BN is an epilogue site: XLA's lowering by the
            # auto rung on one chip (PERF.md section 6, PR 33), the
            # kernel by the force rung, a counted structural demotion
            # when the mesh partitions it
            ce = traced["conv_epilogue"]
            rung = "forced" if force_kernels else "auto_dense"
            if n_dev > 1:
                require(set(ce) == {"structural"},
                        f"partitioned serve took kernels: {ce}")
                fused = 0
            else:
                require(ce.get(rung, 0) > 0
                        and set(ce) <= {rung, "structural"},
                        f"conv_epilogue sites not {rung}: {ce}")
                fused = (ce[rung] // len(ver.batcher.buckets)
                         if force_kernels else 0)
            b = ver.batcher
            placed, _ = b._place_chunk(
                np.zeros((b.buckets[0], hw, hw, 3), np.float32))
            n_mosaic, _ = mosaic_calls(b._fwd.lower(
                net.params, net.states, placed))
            want = expected_mosaic(fused)
            say("serve", f"compiled bucket-{b.buckets[0]} forward: "
                         f"{n_mosaic} Mosaic custom calls (expected "
                         f"{want})")
            require(n_mosaic == want,
                    f"{n_mosaic} Mosaic calls, {want} fused sites")
        finally:
            if srv is not None:
                srv.stop()
            reg.shutdown()
    return {"ok": True, "warmup_s": warm}


# ----------------------------------------------------------------------
#: the published GPT-2-small shape — what models.decoder's
#: learned-position, LayerNorm, MHA block is
GPT2_SMALL = dict(vocab_size=50257, n_layers=12, n_heads=12,
                  d_model=768, d_ff=3072, max_len=1024)


def phase_generate(conf: dict = GPT2_SMALL, *, n_requests=4,
                   max_tokens=32, prompt_len=8, kv_blocks=64,
                   kv_block_size=16, max_seq_len=128,
                   prompt_bucket=16, paged=None) -> dict:
    """``DecoderLM`` behind ``:generate``; then one decode step's
    logits, paged kernel against the dense gather, on one pool.

    ``paged=True`` is the CPU test's way to take the kernel (interpret
    mode); the chip run passes None and requires the auto rung."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.decoder import (DecoderConfig,
                                                   DecoderLM)
    from deeplearning4j_tpu.serving.registry import ModelRegistry
    from deeplearning4j_tpu.serving.server import InferenceServer

    c = DecoderConfig(**conf)
    model = DecoderLM(c)
    gen = {"kv_blocks": kv_blocks, "kv_block_size": kv_block_size,
           "prompt_buckets": (prompt_bucket,),
           "decode_buckets": (n_requests,),
           "max_seq_len": max_seq_len}
    if paged is not None:
        gen["paged"] = paged
    rng = np.random.RandomState(11)
    prompts = [rng.randint(2, c.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]

    before = decisions_now()
    reg = ModelRegistry()
    srv = None
    try:
        t0 = time.perf_counter()
        ver = reg.register("lm", model, generate=gen)
        warm = time.perf_counter() - t0
        traced = decisions_since(before)
        say("generate", f"register + warm-up (prefill bucket "
                        f"{prompt_bucket}, decode bucket {n_requests}): "
                        f"{warm:.1f}s; ladder: paged_attention="
                        f"{traced['paged_attention']} attention="
                        f"{traced['attention']}")
        if paged is None:
            require(traced["paged_attention"].get("auto_fused", 0) > 0,
                    f"decode did not take the paged kernel by the auto "
                    f"rung: {traced['paged_attention']}")
        srv = InferenceServer(reg).start(port=0)

        def generate(i):
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=600)
            try:
                conn.request(
                    "POST", "/v1/models/lm:generate",
                    body=json.dumps({"prompt": prompts[i],
                                     "max_tokens": max_tokens}).encode(),
                    headers={"Content-Type": "application/json"})
                r = conn.getresponse()
                body = r.read().decode()
                require(r.status == 200, f"generate -> {r.status}: "
                                         f"{body[:200]}")
                return [json.loads(ln) for ln in
                        body.strip().splitlines()]
            finally:
                conn.close()

        t0 = time.perf_counter()
        streams = _in_threads(generate, n_requests)
        wall = time.perf_counter() - t0
        counts = []
        for lines in streams:
            toks = [r["token"] for r in lines if "token" in r]
            done = lines[-1]
            require(done.get("done") is True
                    and done["tokens"] == len(toks)
                    and done["reason"] in ("max_tokens", "eos"),
                    f"stream did not end cleanly: {done}")
            require(len(toks) == max_tokens
                    or done["reason"] == "eos",
                    f"{len(toks)} tokens, reason {done['reason']}")
            require(all(0 <= t < c.vocab_size for t in toks),
                    "token id out of range")
            counts.append(len(toks))
        retraces = ver.retraces_since_warmup()
        say("generate", f"{n_requests} concurrent :generate streams "
                        f"to the end: tokens={counts} in {wall:.2f}s, "
                        f"retraces since warm-up: {retraces}")
        require(retraces == 0, f"{retraces} retraces after warm-up")

        # the compiled decode step holds one paged kernel per layer
        eng = ver.batcher.engine
        b = n_requests
        args = (eng.params, eng.pool.arrays,
                np.zeros((b,), np.int32), np.zeros((b,), np.int32),
                np.zeros((b, eng.max_blocks), np.int32),
                jax.random.PRNGKey(0), np.zeros((b,), np.float32),
                np.zeros((b,), np.int32))
        require(eng._paged_now(), "the engine decodes through the "
                                  "dense gather, not the paged kernel")
        n_mosaic, _ = mosaic_calls(eng._decode_jit().lower(*args))
        want = expected_mosaic(c.n_layers)
        say("generate", f"compiled decode step: {n_mosaic} Mosaic "
                        f"custom calls (expected {want}: one per "
                        f"layer)")
        require(n_mosaic == want,
                f"{n_mosaic} Mosaic calls, {want} paged sites")

        # one decode step, both lowerings, on the same random pool
        params = eng.params
        shape = eng.pool.k.shape
        kk, kv, kt = jax.random.split(jax.random.PRNGKey(5), 3)
        k_pool = (0.5 * jax.random.normal(kk, shape)).astype(
            eng.pool.k.dtype)
        v_pool = (0.5 * jax.random.normal(kv, shape)).astype(
            eng.pool.v.dtype)
        # sequence i holds i+1 blocks' worth of tokens minus a few
        lens = np.array([(i % eng.max_blocks + 1) * kv_block_size - 3
                         for i in range(b)], np.int32)
        tables = np.zeros((b, eng.max_blocks), np.int32)
        nxt = 1
        for i in range(b):
            for j in range(-(-int(lens[i] + 1) // kv_block_size)):
                tables[i, j] = nxt
                nxt += 1
        require(nxt <= shape[1], "pool too small for the check")
        tokens = np.asarray(jax.random.randint(
            kt, (b,), 2, c.vocab_size), np.int32)

        def step(paged_flag):
            f = jax.jit(lambda p, kp, vp: model.decode_step(
                p, jnp.asarray(tokens), jnp.asarray(lens), kp, vp,
                jnp.asarray(tables), paged=paged_flag)[0])
            return np.asarray(f(params, k_pool, v_pool), np.float32)

        got, ref = step(True), step(False)
        err = rel_err(got, ref)
        say("generate", f"decode-step logits, paged kernel vs dense "
                        f"gather on one pool (lengths {lens.tolist()}): "
                        f"rel error {err:.2e} (tol {KERNEL_REL_TOL})")
        require(np.all(np.isfinite(got)), "non-finite logits")
        require(err <= KERNEL_REL_TOL,
                f"paged decode step differs from the reference: {err}")
    finally:
        if srv is not None:
            srv.stop()
        reg.shutdown()
    return {"ok": True, "warmup_s": warm, "tokens": counts}


# ----------------------------------------------------------------------
def phase_kernels(*, epilogue_rows=128 * 56 * 56, epilogue_k=64,
                  epilogue_n=256,
                  bn_shapes=((128, 56, 56, 64), (128, 7, 7, 512)),
                  flash_shape=(2, 12, 4096, 64),
                  paged=(4, 12, 64, 64, 16, 8),
                  force_kernels=False) -> dict:
    """The TPU-default kernels one by one, each against its dense
    lowering: the conv epilogue and the pointwise matmul at one
    ResNet-50 shape (its own convs have no bias, so ``conv_epilogue``
    is structural there); the four BN kernels at two ResNet-50 shapes
    (64 channels = half a vreg row; 6272 rows = a partial last block)
    against the f32 lowering — the train phase's whole-step check is
    dominated by the conv weights and would not see a wrong dgamma;
    flash attention forward and dq/dk/dv backward at the first length
    the auto rung takes; the paged decode kernel standalone.

    ``paged`` = (batch, heads, head_dim, pool blocks, block size,
    table width)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import attention, bn_pallas, conv_pallas
    from deeplearning4j_tpu.ops.attention_pallas import (
        paged_attention_reference, paged_decode_attention)
    from deeplearning4j_tpu.parallel.sequence import flash_attention

    out = {}
    key = jax.random.PRNGKey(3)
    kx, kw, kb, kg, kq, kk, kv = jax.random.split(key, 7)
    bf = jnp.bfloat16

    # -- conv family at [rows, K] x [K, N] ------------------------------
    m, k, n = epilogue_rows, epilogue_k, epilogue_n
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(bf)
    w = (jax.random.normal(kw, (k, n), jnp.float32) * 0.1).astype(bf)
    bias = jax.random.normal(kb, (n,), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(kg, (n,), jnp.float32)

    t0 = time.perf_counter()
    got = jax.jit(lambda a, b_, c: conv_pallas._matmul_epilogue(
        a, b_, c, "relu"))(x, w, bias)
    ref = jax.jit(lambda a, b_, c: jnp.maximum(
        jnp.dot(a, b_, preferred_element_type=jnp.float32) + c, 0
    ).astype(bf))(x, w, bias)
    out["matmul_epilogue"] = rel_err(got, ref)
    say("kernels", f"_matmul_epilogue [{m},{k}]x[{k},{n}] relu vs "
                   f"dense: rel {out['matmul_epilogue']:.2e} "
                   f"({time.perf_counter() - t0:.1f}s)")

    z = ref                                        # [rows, N] bf16

    def ssa_loss(fn):
        def f(z_, s_, b_):
            y = fn(z_, s_, b_)
            return jnp.sum(y.astype(jnp.float32) ** 2) * 1e-6, y
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    t0 = time.perf_counter()
    (_, y_k), g_k = ssa_loss(lambda z_, s_, b_: (
        conv_pallas.scale_shift_act(z_, s_, b_, "relu")))(z, scale, bias)
    (_, y_d), g_d = ssa_loss(lambda z_, s_, b_: jnp.maximum(
        z_.astype(jnp.float32) * s_ + b_, 0).astype(z_.dtype))(
            z, scale, bias)
    out["scale_shift_act"] = rel_err(y_k, y_d)
    out["scale_shift_act_grads"] = max(
        rel_err(a, b_) for a, b_ in zip(g_k, g_d))
    say("kernels", f"scale_shift_act [{m},{n}] relu vs dense: fwd rel "
                   f"{out['scale_shift_act']:.2e}, dx/dscale/dshift "
                   f"rel {out['scale_shift_act_grads']:.2e} "
                   f"({time.perf_counter() - t0:.1f}s)")

    # -- BN statistics / normalize / backward sums / dx ------------------
    # against f32 math on the same bf16-rounded input, under a linear
    # loss (a well-conditioned dgamma; the bf16 dense lowering's own
    # dgamma is the noisier of the two)
    def bn_grads(fn, x_, wt):
        def f(x__, g_, b_):
            y, mean, var = fn(x__, g_, b_)
            return (jnp.sum(y.astype(jnp.float32) * wt)
                    + jnp.sum(mean) + jnp.sum(var))
        c = x_.shape[-1]
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(
            x_, jnp.full((c,), 1.5), jnp.full((c,), 0.25))

    for shape in bn_shapes:
        t0 = time.perf_counter()
        kx2, kw2 = jax.random.split(jax.random.fold_in(key, shape[-1]))
        xb = (0.5 + 2.0 * jax.random.normal(kx2, shape, jnp.float32)
              ).astype(bf)
        wt = jax.random.normal(kw2, shape, jnp.float32)
        with ladder(fused_conv="1", fused_bn_bwd="1"):
            got = bn_grads(lambda *a: bn_pallas.bn_train_normalize(
                *a, 1e-5), xb, wt)
        with ladder(fused_conv="0", fused_bn_bwd="0"):
            ref = bn_grads(lambda *a: bn_pallas.bn_forward_math(
                *a, 1e-5)[:3], xb.astype(jnp.float32), wt)
        name = "bn_" + "x".join(str(d) for d in shape)
        out[name] = max(rel_err(a, r) for a, r in zip(got, ref))
        say("kernels", f"BN kernels {shape} bf16 vs f32 dense, "
                       f"dx/dgamma/dbeta: worst rel {out[name]:.2e} "
                       f"({time.perf_counter() - t0:.1f}s)")

    # -- flash attention, forward + dq/dk/dv backward -------------------
    b, h, t, d = flash_shape
    q = jax.random.normal(kq, (b, h, t, d), jnp.float32).astype(bf)
    kk_ = jax.random.normal(kk, (b, h, t, d), jnp.float32).astype(bf)
    vv = jax.random.normal(kv, (b, h, t, d), jnp.float32).astype(bf)
    key_mask = jnp.ones((b, t), jnp.float32).at[:, t - t // 8:].set(0.0)

    def attn_grads(fn):
        def f(q_, k_, v_):
            o = fn(q_, k_, v_)
            return jnp.sum(jnp.sin(o.astype(jnp.float32))), o
        (_, o), g = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True))(q, kk_, vv)
        return (o,) + tuple(g)

    # (a) the user's entry point: the auto rung picks the lowering
    t0 = time.perf_counter()
    before = decisions_now()
    gates = {"flash_attention": "1"} if force_kernels else {}
    with ladder(**gates):
        got = attn_grads(lambda q_, k_, v_: (
            attention.dot_product_attention(
                q_, k_, v_, mask=key_mask[:, None, None, :])))
    took = decisions_since(before)["attention"]
    rung = "forced" if force_kernels else "auto_fused"
    require(took.get(rung, 0) > 0,
            f"dot_product_attention at t={t} did not take flash by "
            f"{rung}: {took}")
    with ladder(flash_attention="0"):
        ref = attn_grads(lambda q_, k_, v_: (
            attention.dot_product_attention(
                q_, k_, v_, mask=key_mask[:, None, None, :])))
    out["flash_keymask"] = max(rel_err(a, r) for a, r in zip(got, ref))
    say("kernels", f"dot_product_attention {flash_shape} key-masked, "
                   f"flash ({took}) vs dense, out/dq/dk/dv: worst rel "
                   f"{out['flash_keymask']:.2e} "
                   f"({time.perf_counter() - t0:.1f}s)")

    # (b) causal: dot_product_attention has no causal argument (a
    # [t, t] mask is not a key mask, so it stays dense) — the causal
    # kernels are what ring/Ulysses attention call
    t0 = time.perf_counter()
    got = attn_grads(lambda q_, k_, v_: flash_attention(q_, k_, v_, True))
    causal = jnp.tril(jnp.ones((t, t), jnp.float32))[None, None]
    with ladder(flash_attention="0"):
        ref = attn_grads(lambda q_, k_, v_: (
            attention.dot_product_attention(q_, k_, v_, mask=causal)))
    out["flash_causal"] = max(rel_err(a, r) for a, r in zip(got, ref))
    say("kernels", f"flash_attention {flash_shape} causal vs dense, "
                   f"out/dq/dk/dv: worst rel {out['flash_causal']:.2e} "
                   f"({time.perf_counter() - t0:.1f}s)")

    # -- paged decode attention, standalone -----------------------------
    # full rows, a row of length 1 (what a dead row of the bucket is)
    # and a ragged one; the float32 pool and the bf16 pool that the
    # benchmark's engine holds
    pb, ph, pd, nb, bs, width = paged
    kq2, kk2, kv2 = jax.random.split(kq, 3)
    pq = jax.random.normal(kq2, (pb, ph, pd), jnp.float32)
    full = width * bs
    lens = np.array([full if i % 2 == 0 else 1 if i == 1
                     else 1 + (7 * (i + 1)) % full
                     for i in range(pb)], np.int32)
    tables = np.zeros((pb, width), np.int32)
    nxt = 1
    for i in range(pb):
        for j in range(-(-int(lens[i]) // bs)):
            tables[i, j] = nxt
            nxt += 1
    require(nxt <= nb, "pool too small for the paged check")
    # two layers of a pool as KVBlockPool stores it, the second read
    k_f32 = jax.random.normal(kk2, (2, nb, bs, ph * pd), jnp.float32)
    v_f32 = jax.random.normal(kv2, (2, nb, bs, ph * pd), jnp.float32)
    for name, dtype in (("paged_decode", jnp.float32),
                        ("paged_decode_bf16", bf)):
        args = (pq, k_f32.astype(dtype), v_f32.astype(dtype),
                jnp.asarray(tables), jnp.asarray(lens), 1)
        t0 = time.perf_counter()
        got = jax.jit(paged_decode_attention)(*args)
        ref = jax.jit(paged_attention_reference)(*args)
        out[name] = rel_err(got, ref)
        say("kernels", f"paged_decode_attention b={pb} h={ph} d={pd} "
                       f"{jnp.dtype(dtype).name} pool, lengths "
                       f"{lens.tolist()} vs dense gather: rel "
                       f"{out[name]:.2e} "
                       f"({time.perf_counter() - t0:.1f}s)")

    bad = {k_: v_ for k_, v_ in out.items()
           if not (np.isfinite(v_) and v_ <= KERNEL_REL_TOL)}
    require(not bad, f"kernels outside tol {KERNEL_REL_TOL}: {bad}")
    return {"ok": True, **out}


# ----------------------------------------------------------------------
def phase_four_chip(*, batch=256, hw=224, classes=1000, stages=None,
                    steps=3, n=4) -> dict:
    """ResNet-50 data-parallel over ``n`` devices through
    ``ParallelWrapper``, against the same global batch on one device;
    then the driver's sharded dryrun on the real devices."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.parallel import ParallelWrapper

    devs = jax.devices()[:n]
    x, y, ds_dev = _image_batch(batch, hw, classes, seed=1)

    # one device, dense ladder: under GSPMD the kernels demote, so
    # this is the lowering the DP step must reproduce
    with ladder(fused_conv="0", fused_bn_bwd="0"):
        one = _resnet(hw, classes, stages)
        loss_one, dnorm_one, wall_one = _step1(one, ds_dev)
    del one, ds_dev
    gc.collect()
    say("four-chip", f"one device, global batch {batch}, dense ladder: "
                     f"loss={loss_one:.4f} ({wall_one:.1f}s)")

    before = decisions_now()
    net = _resnet(hw, classes, stages)
    pw = ParallelWrapper.Builder(net).workers(n).build()
    placed = pw._shard_dataset(DataSet(x, y))
    batch_devs = {d.id for d in placed.features.sharding.device_set}
    require(len(batch_devs) == n,
            f"batch lives on devices {sorted(batch_devs)}")
    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        pw.fit_batch(placed)
        losses.append(float(net.score()))
        require(np.isfinite(losses[-1]), f"DP loss {losses[-1]}")
        if i == 0:
            first = time.perf_counter() - t0
    traced = decisions_since(before)
    param_devs = set()
    for leaf in jax.tree_util.tree_leaves(net.params):
        param_devs |= {d.id for d in leaf.sharding.device_set}
    require(len(param_devs) == n,
            f"params live on devices {sorted(param_devs)}")
    say("four-chip", f"ParallelWrapper workers={n} "
                     f"exchange={pw.update_exchange.value}: first step "
                     f"{first:.1f}s, losses "
                     + ", ".join(f"{v:.4f}" for v in losses)
                     + f"; params on devices {sorted(param_devs)}, "
                     f"batch on {sorted(batch_devs)}")
    for fam in ("bn_fwd", "bn_bwd"):
        require(set(traced[fam]) == {"structural"},
                f"partitioned step took {fam} kernels: {traced[fam]}")
    say("four-chip", f"ladder under GSPMD: bn_fwd={traced['bn_fwd']} "
                     f"bn_bwd={traced['bn_bwd']} (Mosaic kernels "
                     f"cannot be auto-partitioned)")
    # the CPU backend reports no memory_stats; a TPU always does
    stats = {d.id: d.memory_stats() for d in devs}
    used = {i: int(st["bytes_in_use"]) for i, st in stats.items()
            if st is not None}
    require(len(used) == n or devs[0].platform != "tpu",
            f"memory_stats missing on a TPU: {used}")
    if used:
        say("four-chip", "bytes_in_use per device: "
                         + ", ".join(f"{i}: {b_ / 2**20:.0f} MiB"
                                     for i, b_ in sorted(used.items())))
        require(min(used.values()) > 0
                and max(used.values()) < 0.5 * sum(used.values()),
                f"one device holds most of the bytes: {used}")
    e = abs(losses[0] - loss_one) / max(abs(loss_one), 1e-30)
    say("four-chip", f"DP step-1 loss vs one device: {losses[0]:.4f} "
                     f"vs {loss_one:.4f}, rel {e:.2e} "
                     f"(tol {STEP_REL_TOL})")
    require(e <= STEP_REL_TOL, "DP loss differs from one-device loss")
    pw.shutdown()
    del net, pw, placed
    gc.collect()

    import __graft_entry__
    __graft_entry__.dryrun_multichip(n)
    return {"ok": True, "losses": losses, "one_device_loss": loss_one}


# ----------------------------------------------------------------------
#: a Falcon-H1 block at a small width: every published ratio (5 query
#: heads a KV head, 2 state groups, convolution 4) and a head state of
#: whole float32 tiles, so the state-update kernel is on the path
_H1_PUBLISHED = __file__.replace("chip_smoke.py",
                                 "chipbench/configs/falcon-h1-34b.json")
H1_SMALL = dict(vocab_size=1024, num_hidden_layers=2, hidden_size=256,
                num_attention_heads=10, num_key_value_heads=2,
                head_dim=64, intermediate_size=512, mamba_d_ssm=256,
                mamba_n_heads=4, mamba_d_head=64, mamba_d_state=128,
                init_std=0.05)


def phase_hybrid(conf: dict = H1_SMALL, *, gqa=(8, 20, 4, 128, 80, 16, 6),
                 state=(2, 6, 32, 128, 256, 2, 4), prompt_len=37,
                 prompt_bucket=128, steps=8, block=16) -> dict:
    """The hybrid block's two decode kernels against their
    ``jax.numpy`` forms, then ``FalconH1LM`` (prefill, commit into K/V
    blocks and a state slot, ``steps`` decode steps) against the plain
    reference's full forward. ``gqa`` = (rows, query heads, KV heads,
    head dim, blocks, block, table width); ``state`` = (layers, slots,
    heads, p, n, groups, rows)."""
    import jax
    import jax.numpy as jnp

    from chipbench.models.falcon_h1 import program_layout
    from chipbench.reference import falcon_h1 as ref
    from deeplearning4j_tpu.models.falcon_h1 import (FalconH1Config,
                                                     FalconH1LM)
    from deeplearning4j_tpu.ops.attention_pallas import (
        paged_attention_reference, paged_decode_attention)
    from deeplearning4j_tpu.ops.ssm_pallas import (
        ssm_state_update_pallas, ssm_state_update_reference)
    from deeplearning4j_tpu.serving.kvcache import KVBlockPool

    out = {}
    key = jax.random.PRNGKey(23)
    # -- grouped-query paged attention ----------------------------------
    pb, hq, hkv, pd, nb, bs, width = gqa
    kq, kk, kv, key = jax.random.split(key, 4)
    lens = np.array([width * bs if i % 2 == 0 else 1 + (11 * i) % (width * bs)
                     for i in range(pb)], np.int32)
    tables = np.zeros((pb, width), np.int32)
    nxt = 1
    for i in range(pb):
        for j in range(-(-int(lens[i]) // bs)):
            tables[i, j] = nxt
            nxt += 1
    require(nxt <= nb, "pool too small for the grouped-query check")
    args = (jax.random.normal(kq, (pb, hq, pd), jnp.float32),
            jax.random.normal(kk, (2, nb, bs, hkv * pd)).astype(jnp.bfloat16),
            jax.random.normal(kv, (2, nb, bs, hkv * pd)).astype(jnp.bfloat16),
            jnp.asarray(tables), jnp.asarray(lens), 1)
    out["paged_gqa"] = rel_err(jax.jit(paged_decode_attention)(*args),
                               jax.jit(paged_attention_reference)(*args))
    say("hybrid", f"paged_decode_attention {hq} query heads on {hkv} KV "
                  f"heads of {pd}, bf16 pool, lengths {lens.tolist()} vs "
                  f"dense gather: rel {out['paged_gqa']:.2e}")

    # -- the state update, in place ---------------------------------------
    nl, ns, nh, p, n, g, rows = state
    ks = jax.random.split(key, 7)
    pool = jax.random.normal(ks[0], (nl, ns, nh, p, n), jnp.float32)
    slots = jnp.asarray([ns - 1, 1] + [0] * (rows - 2), jnp.int32)
    dt = jax.random.uniform(ks[1], (rows, nh), jnp.float32, 1e-3, 0.5)
    ops = (jax.random.normal(ks[2], (rows, nh, p), jnp.float32), dt,
           jnp.exp(-4.0 * dt),
           jax.random.normal(ks[3], (rows, g, n), jnp.float32),
           jax.random.normal(ks[4], (rows, g, n), jnp.float32))
    want_s, want_y = jax.jit(ssm_state_update_reference,
                             static_argnums=1)(pool, nl - 1, slots, *ops)
    got_s, got_y = jax.jit(ssm_state_update_pallas,
                           static_argnums=1)(pool, nl - 1, slots, *ops)
    live = np.asarray([ns - 1, 1])
    out["ssm_state_y"] = rel_err(got_y[:2], want_y[:2])
    out["ssm_state_s"] = rel_err(got_s[nl - 1, live], want_s[nl - 1, live])
    rest = [i for i in range(1, ns) if i not in live]
    require(bool(jnp.all(got_s[:nl - 1] == pool[:nl - 1]))
            and bool(jnp.all(got_s[nl - 1, rest] == pool[nl - 1, rest])),
            "the state update touched a slot no live row named")
    say("hybrid", f"pallas.ssm_state_update {nh} heads of [{p}, {n}], "
                  f"{rows} rows of which 2 live, vs its jax.numpy form: "
                  f"y rel {out['ssm_state_y']:.2e}, state rel "
                  f"{out['ssm_state_s']:.2e}; other slots untouched")

    # -- the model through blocks and a slot, against the reference --------
    cfg = dict(json.load(open(_H1_PUBLISHED)), **conf)
    weights = ref.make_params(cfg, 29)
    params = program_layout(weights)
    model = FalconH1LM(FalconH1Config.from_published(
        cfg, max_len=prompt_bucket + steps))
    total = prompt_len + steps
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(31), (total,), 0, cfg["vocab_size"]), np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.forward(cfg, weights, jnp.asarray(tokens)))
    padded = np.zeros((1, prompt_bucket), np.int32)
    padded[0, :prompt_len] = tokens[:prompt_len]
    last, k, v, ssm, conv = jax.jit(model.prefill)(
        params, padded, np.asarray([prompt_len], np.int32))
    n_blocks = -(-total // block)
    cache_pool = KVBlockPool(
        cfg["num_hidden_layers"], n_blocks + 1, block,
        cfg["num_key_value_heads"], cfg["head_dim"], dtype=jnp.bfloat16,
        name="smoke-h1", state=model.state_shapes(), state_slots=2)
    idx = np.arange(prompt_bucket)
    at = np.where(idx < prompt_len, block + idx, 0)     # blocks 1.., in order
    kp, vp, s_, c_ = cache_pool.arrays
    flat = (kp.shape[0], -1, kp.shape[3])
    cache = (kp.reshape(flat).at[:, at].set(
                 k[:, 0].reshape(flat).astype(kp.dtype)).reshape(kp.shape),
             vp.reshape(flat).at[:, at].set(
                 v[:, 0].reshape(flat).astype(vp.dtype)).reshape(vp.shape),
             s_.at[:, 1].set(ssm[:, 0]), c_.at[:, 1].set(conv[:, 0]))
    table = np.arange(1, n_blocks + 1, dtype=np.int32)[None]
    step = jax.jit(lambda *a: model.decode_step(*a, paged=True))
    got = [np.asarray(last[0])]
    for pos in range(prompt_len, total - 1):
        logits, *cache = step(params, tokens[pos:pos + 1],
                              np.asarray([pos], np.int32), *cache, table,
                              np.asarray([1], np.int32))
        got.append(np.asarray(logits[0]))
    out["model"] = rel_err(np.stack(got), want[prompt_len - 1:total - 1])
    say("hybrid", f"FalconH1LM at width {cfg['hidden_size']}: prefill of "
                  f"{prompt_len} in a bucket of {prompt_bucket} + "
                  f"{len(got) - 1} decode steps through the cache vs the "
                  f"plain reference's full forward: rel {out['model']:.2e}")
    bad = {k_: v_ for k_, v_ in out.items()
           if not (np.isfinite(v_) and v_ <= KERNEL_REL_TOL)}
    require(not bad, f"hybrid checks outside tol {KERNEL_REL_TOL}: {bad}")
    return {"ok": True, **out}


# ----------------------------------------------------------------------
def main() -> int:
    t_start = time.perf_counter()
    dev = require_tpu()
    import jax

    import deeplearning4j_tpu  # noqa: F401  (places the compile cache)
    print_header(dev)

    walls = {}

    def timed(name, fn, **kw):
        t0 = time.perf_counter()
        res = fn(**kw)
        walls[name] = time.perf_counter() - t0
        say(name, f"ok ({walls[name]:.1f}s)")
        return res

    timed("native", phase_native)
    train = timed("train", phase_train)
    timed("serve", phase_serve, net=train.pop("net"))
    del train
    gc.collect()
    timed("generate", phase_generate)
    gc.collect()
    timed("hybrid", phase_hybrid)
    gc.collect()
    timed("kernels", phase_kernels)
    gc.collect()
    if dev["count"] >= 4:
        timed("four-chip", phase_four_chip)
    else:
        say("four-chip", f"devices={dev['count']}, four-chip phase "
                         f"not run")

    say("ladder", "kernel_select decisions over the whole run:")
    for fam, counts in decisions_now().items():
        say("ladder", f"  {fam}: {counts}")
    for d in jax.devices():
        stats = d.memory_stats()
        say("memory", f"device {d.id}: peak_bytes_in_use="
                      f"{stats['peak_bytes_in_use']} "
                      f"peak_bytes_reserved="
                      f"{stats['peak_bytes_reserved']} "
                      f"bytes_limit={stats['bytes_limit']}")
    say("total", " ".join(f"{k}={v:.1f}s" for k, v in walls.items())
                 + f" all={time.perf_counter() - t_start:.1f}s; "
                 f"compile cache entries at end: {cache_entries()}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
