"""Window driver for generation cells: one thread plays the mix's clients through the
program's own submit call and reads every stream's tokens on the client's clock. A client
sends its next request when its last one ends, and a request is submitted only when the
pool can hold it to its last token: until then it waits in the generator's own queue,
first come first served. A request fails only by error."""
import collections
import queue
import threading
import time

import numpy as np

from chipbench import harness, traffic


class Request:
    def __init__(self, spec):
        self.spec, self.prompt, self.max_tokens = spec, spec["prompt"], spec["max_tokens"]
        self.stream, self.error, self.done, self.cancelled = None, None, False, False
        self.times, self.tokens = [], []


def run(ctx):
    cfg, mix, seed, seconds = ctx["cfg"], ctx["mix"], ctx["seed"], ctx["seconds"]
    ref = harness.module("reference", cfg["reference"])
    prog = (ctx.get("build") or harness.module("models", cfg["builder"]).build)(
        cfg, mix, seed, ctx["chips"])
    if mix["clients"] > cfg["max_in_flight"]:
        raise RuntimeError("more clients than may be in flight")
    waves = traffic.waves(mix, seed, cfg["vocab_size"])
    room = prog.pool.usable_blocks
    blocks = lambda r: prog.pool.blocks_for(len(r.prompt) + r.max_tokens)  # noqa: E731

    setup_s = time.perf_counter() - ctx["t_start"]
    t0 = time.perf_counter()
    waiting = {c: collections.deque() for c in range(mix["clients"])}
    idle = dict.fromkeys(waiting)           # the clients with no request out, longest idle first
    flying, issued, tracer, span = [], [], None, None
    reserved = reserved_peak = live_peak = 0
    closing = False

    def issue(r):
        nonlocal reserved, reserved_peak
        try:
            r.stream = prog.submit(r.prompt, r.max_tokens)
            flying.append(r)
            reserved += blocks(r)
            reserved_peak = max(reserved_peak, reserved)
        except Exception as e:  # noqa: BLE001 - a refused request is a failed one
            r.error, r.done = e, True
        issued.append(r)

    while True:
        now = time.perf_counter()
        if ctx["trace"]:
            if tracer is None and now - t0 >= 0.3 * seconds:
                tracer = threading.Thread(target=harness.start_trace, args=(ctx,))
                tracer.start()
            elif span is None and tracer is not None and not tracer.is_alive():
                span = harness.window_span(ctx)
                span.__enter__()
                t_span = now
            elif span not in (None, "closed") and now - t_span >= cfg["trace_seconds"]:
                span.__exit__(None, None, None)
                span = "closed"
                tracer = threading.Thread(target=harness.stop_trace, args=(ctx,))
                tracer.start()
        if not closing:
            while any(not waiting[c] for c in idle):
                for spec in next(waves):
                    waiting[spec["client"]].append(Request(spec))
            for c in list(idle):
                if blocks(waiting[c][0]) > room:
                    raise RuntimeError("a request that the whole pool cannot hold")
                if reserved + blocks(waiting[c][0]) > room:
                    break                           # waits for blocks, first come first
                del idle[c]
                issue(waiting[c].popleft())
        for r in flying:
            try:
                while not r.done:
                    tok = r.stream.next(timeout=0)
                    if tok is None:
                        r.done = True
                    else:
                        r.times.append(time.perf_counter())
                        r.tokens.append(tok)
            except queue.Empty:
                pass
            except Exception as e:  # noqa: BLE001
                r.error, r.done = e, True
        live_peak = max(live_peak, prog.pool.live_blocks)
        for r in [r for r in flying if r.done]:
            flying.remove(r)
            reserved -= blocks(r)
            idle[r.spec["client"]] = None
        if now - t0 >= seconds:
            if not closing:
                closing = True
                for r in flying:
                    r.cancelled = True
                    r.stream.cancel()
            if not flying:
                break
        time.sleep(0.0005)
    t_end = t0 + seconds
    if tracer is not None:
        if span not in (None, "closed"):
            span.__exit__(None, None, None)
            harness.stop_trace(ctx)
        tracer.join()

    capacity = prog.pool.usable_blocks
    print(f"kv pool: peak {live_peak} blocks live, {reserved_peak} reserved, "
          f"of {capacity}", flush=True)
    if max(live_peak, reserved_peak) > capacity:
        raise RuntimeError("the pool's peak passed its capacity")
    if prog.compiles_in_window():
        raise RuntimeError(f"{prog.compiles_in_window()} compiles inside the window")
    whole = [r for r in issued if not r.cancelled]
    vocab = cfg["vocab_size"]
    sound = lambda r: r.error is None and all(0 <= t < vocab for t in r.tokens)  # noqa: E731
    bad = [r for r in whole if not sound(r) or r.stream.reason != "max_tokens"
           or len(r.tokens) != r.max_tokens]
    served = [r for r in issued if r.tokens and sound(r) and r not in bad]
    memory = harness.memory_peak()
    prog.close()
    del prog

    stamps = np.concatenate([np.asarray(r.times) for r in issued if r.times] or [np.zeros(0)])
    gaps = np.concatenate([np.diff([t for t in r.times if t <= t_end]) for r in issued
                           if len(r.times) > 1] or [np.zeros(0)])
    e2e = {"setup_s": setup_s, "gen_tok_per_s": float(np.sum(stamps <= t_end)) / seconds,
           "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3}
    pad = min(cfg["max_len"], -(-(mix["prompt"]["hi"] + mix["output"]["hi"]) // 128) * 128)
    read = numbers(cfg, ref, seed, served, pad)
    ctx["served"] = (served, pad) if ctx.get("keep") else None
    records = {"t0": t0, "t_end": t_end,
               "requests": [{"prompt": len(r.prompt), "times": r.times} for r in issued]}
    return {"attempted": len(whole), "failed": len(bad), "memory_peak_bytes": memory,
            "checks": held(read, cfg["limits"]), "numbers": read, "end_to_end": e2e,
            "records": records}


def numbers(cfg, ref, seed, served, pad, control=False):
    """Every token that the window served, of finished and of cancelled requests, against
    the plain reference (float32 at ``highest``, one pass over each prompt with its served
    tokens): the mean square, the mean and the widest of the gaps by which a served
    token's logit lies below the reference's best at its position. With ``control`` the
    token judged at each position is the first choice of the bfloat16 forward."""
    if not served:
        return {}
    params = ref.make_params(cfg, seed)
    gaps = []
    for r in served:
        ids = np.zeros(pad, np.int32)
        ids[:len(r.prompt) + len(r.tokens)] = list(r.prompt) + list(r.tokens)
        got = np.asarray(ref.served_gaps(cfg, params, ids, control))
        gaps.append(got[len(r.prompt) - 1:len(r.prompt) - 1 + len(r.tokens)])
    gaps = np.concatenate(gaps).astype(np.float64)
    return {"served_gap_sq": float(np.mean(np.square(gaps))), "served_gap_mean": float(np.mean(gaps)),
            "served_gap_max": float(np.max(gaps)), "served_tokens": len(gaps)}


def held(out, limits):
    """Each number that the configuration gives a limit, beside it; one that could not be
    read is no proof."""
    return {k: [out.get(k, float("inf")), limit] for k, limit in limits.items()}


def controls(ctx, ref, res):
    """What the control reads on the same prompts and tokens."""
    return {"control_bf16": numbers(ctx["cfg"], ref, ctx["seed"], *ctx["served"], control=True)}
