#!/usr/bin/env python
"""Gate a fresh bench JSON against a baseline: exit non-zero on >N%
throughput (or step-time) regression.

The companion of ``bench.py``'s new ``meta`` block: once rounds are
comparable run-to-run, a regression becomes a checkable claim instead
of a diff someone eyeballs. Usage:

    python scripts/check_bench_regression.py BASELINE.json FRESH.json
    python scripts/check_bench_regression.py --threshold 5 r04.json r05.json

Accepted file shapes (auto-detected):

- a raw ``bench.py`` output line: ``{"metric": ..., "value": ...}``
- the BENCH_r*.json driver wrapper: ``{"n", "cmd", "rc", "tail",
  "parsed"}`` — ``parsed`` is used; if absent, the last JSON line in
  ``tail`` is.

Comparison: for every shared numeric metric with known polarity —
throughput-like (higher is better: ``value``, ``*_ips``, ``tflops``,
``throughput_rps``) and time-like (lower is better: ``*_ms``,
``*_us``, ``*_seconds``, ``*_pct`` overhead figures) — the fresh run
must not regress by more than ``--threshold`` percent. Improvements
never fail. Exit 0 = clean, 1 = regression(s), 2 = unusable input.

The PR-9 observatory blocks are understood natively: in ``scaling``,
per-size ``efficiency`` entries are higher-is-better and ``skew``
entries lower-is-better (matched on the full dotted path, since the
leaves are bare size/worker labels); ``step_breakdown`` phase means
gate as time-like seconds.  The ``fault_tolerance`` block's stall /
ratio / resume-latency figures gate as lower-is-better, as do any
``lost_steps`` counts.  The ISSUE-12 ``scaling_2d`` block gates
per-mode ``step_seconds`` / ``throughput_sps`` with the usual
polarities and its ``cross_axis`` / ``model_axis_update_bytes``
figures as lower-is-better (the 2D wire invariant: the update
exchange must not start crossing the model axis).  The ISSUE-13
``conv_kernels`` block gates with step time / compiled ``temp_bytes``
/ cost-analysis ``bytes_accessed`` lower-is-better and
``pct_of_roof`` / ``speedup`` / ``bytes_ratio`` higher-is-better —
the fused-epilogue claim is precisely "fewer HBM bytes, closer to
the roof".  The ISSUE-15 ``serving`` block gates its open-loop
percentiles (``p50/p95/p99_ms``) lower-is-better and
``goodput_rps`` / ``in_slo_pct`` / ``occupancy_mean`` / the
residency ``savings_ratio`` and
serialization ``speedup`` higher-is-better — the continuous-batching
claim is "lower tail latency AND more useful completions per second
at the same offered load".  The ISSUE-17
``serving_observatory`` block gates its tracing-on/off p50 pair
(``p50_on_ms`` / ``p50_off_ms``) and ``trace_overhead_pct``
lower-is-better via the usual ``_ms`` / ``overhead`` rules — the
``_pct`` leaf compares in absolute points, holding the "tracing
default-on costs ≤1% on the predict hot path" claim round over
round.  The ISSUE-16
``generative`` block gates decode ``goodput_tokens_per_s`` and
``occupancy_mean`` higher-is-better; ``ttft_*_ms`` /
``intertoken_*_ms`` / the paged-vs-dense ``*_step_ms`` pair and any
``shed_rate`` lower-is-better — the paged-KV claim is "more tokens
per second at lower streaming tail latency, without shedding while
the pool sits half empty".  The ISSUE-18 ``pipeline`` block gates
its per-leg ``step_seconds`` / ``stage_idle_ms`` lower-is-better and
``throughput_rows_per_s`` higher-is-better via the usual rules, plus
``bubble_fraction`` and any scalar ``residency`` figure
lower-is-better — the 1F1B claim is "same bubble as GPipe, strictly
lower peak activation residency, no throughput give-back".  The
ISSUE-20 ``encoded`` block gates its ``wire_bytes`` /
``bytes_per_step`` (both arms and the ``dense_wire_bytes``
counterfactual) lower-is-better and ``compression_ratio``
higher-is-better — the compressed-collective claim is "strictly
fewer bytes on the data axis at the same step count, loss curve
within tolerance of uncompressed".

When baseline and fresh disagree on ``meta.proxy`` (one is a
CPU-proxy round, the other a real-chip round) the comparison is
skipped with a loud note and exit 0 — cross-rig numbers differ for
rig reasons, not code reasons.

Self-test (tier-1, no accelerator): two synthetic driver-wrapper
rounds 0.5% apart must pass at the default threshold and flag the
throughput drop at a tight one (see tests/test_diagnostics.py).
"""
from __future__ import annotations

import argparse
import json
import sys

#: metrics where larger is better (substring match on the key)
HIGHER_BETTER = ("value", "tflops", "throughput", "_ips", "_rps",
                 "efficiency", "savings_ratio", "pct_of_roof",
                 "speedup", "bytes_ratio", "goodput", "in_slo_pct",
                 "occupancy", "compression_ratio")
#: metrics where smaller is better
LOWER_BETTER = ("_ms", "_us", "_seconds", "overhead", "stall", "skew",
                "_bytes_per_chip", "lost_steps", "cross_axis",
                "model_axis_update_bytes", "temp_bytes",
                "bytes_accessed", "shed", "bubble_fraction",
                "residency", "wire_bytes", "bytes_per_step")
#: keys that are identity/config, never compared; "canary" keys are
#: clock-path checks documented as dispatch-noise-dominated
SKIP = ("metric", "unit", "n_trials", "vs_baseline", "meta", "min",
        "max", "telemetry", "memory", "canary")


def load_bench(path: str) -> dict:
    """The bench record from either a raw bench.py JSON line or a
    BENCH_r*.json driver wrapper."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: expected a JSON object")
    if "metric" in doc:
        return doc
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        return parsed
    # wrapper without parsed: last JSON object line in the tail
    for line in reversed((doc.get("tail") or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "metric" in rec:
                return rec
    raise ValueError(f"{path}: no bench record found (neither a raw "
                     f"line, nor wrapper 'parsed'/'tail')")


def _flatten(rec: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in rec.items():
        if k in SKIP or any(s in k for s in ("canary",)):
            continue
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key + "."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = float(v)
    return out


def _polarity(key: str):
    # leaf first; nested blocks whose leaves are bare labels (the
    # `scaling` block's `efficiency.8`, `skew_seconds.3` — per-size /
    # per-worker maps) fall back to a full-path match
    for probe in (key.rsplit(".", 1)[-1], key):
        for pat in LOWER_BETTER:
            if pat in probe:
                return -1
        for pat in HIGHER_BETTER:
            if pat in probe:
                return +1
    return 0           # unknown polarity: informational only


def compare(baseline: dict, fresh: dict, threshold_pct: float):
    """(regressions, improvements, skipped) — each a list of
    (key, base, fresh, delta_pct) tuples; delta_pct is signed so that
    negative always means 'got worse'."""
    base_f, fresh_f = _flatten(baseline), _flatten(fresh)
    regressions, improvements, skipped = [], [], []
    for key in sorted(set(base_f) & set(fresh_f)):
        b, f = base_f[key], fresh_f[key]
        pol = _polarity(key)
        if pol == 0 or (b == 0 and not key.endswith("_pct")):
            skipped.append((key, b, f, 0.0))
            continue
        if key.rsplit(".", 1)[-1].endswith("_pct"):
            # already a percentage: compare in absolute points (a
            # noise-floor move like -0.9% -> 1.4% must not read as a
            # -256% relative regression)
            delta = pol * (f - b)
        else:
            delta = pol * (f - b) / abs(b) * 100     # + = improved
        row = (key, b, f, delta)
        if delta < -threshold_pct:
            regressions.append(row)
        elif delta > 0:
            improvements.append(row)
    return regressions, improvements, skipped


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("baseline", help="baseline bench JSON")
    ap.add_argument("fresh", help="fresh bench JSON to gate")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="max tolerated regression, percent "
                         "(default 10)")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="only print regressions")
    args = ap.parse_args(argv)
    try:
        base = load_bench(args.baseline)
        fresh = load_bench(args.fresh)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    base_proxy = (base.get("meta") or {}).get("proxy")
    fresh_proxy = (fresh.get("meta") or {}).get("proxy")
    if base_proxy is not None and fresh_proxy is not None and \
            base_proxy != fresh_proxy:
        # one round ran on the chip, the other on the CPU proxy —
        # every number differs by orders of magnitude for rig
        # reasons, so a diff would be pure noise. Loud skip, clean
        # exit: this is "not comparable", not "regressed".
        print("SKIP: baseline and fresh disagree on meta.proxy "
              f"(baseline proxy={base_proxy}, fresh "
              f"proxy={fresh_proxy}) — a CPU-proxy round and a TPU "
              "round are not comparable; not gating.")
        return 0
    if base.get("metric") != fresh.get("metric"):
        print(f"error: metric mismatch — baseline "
              f"{base.get('metric')!r} vs fresh "
              f"{fresh.get('metric')!r}", file=sys.stderr)
        return 2
    regs, imps, _ = compare(base, fresh, args.threshold)
    for key, b, f, d in regs:
        print(f"REGRESSION {key}: {b:g} -> {f:g} ({d:+.1f}% vs "
              f"-{args.threshold:g}% allowed)")
    if not args.quiet:
        for key, b, f, d in imps:
            print(f"ok         {key}: {b:g} -> {f:g} ({d:+.1f}%)")
    if regs:
        print(f"{len(regs)} regression(s) beyond "
              f"{args.threshold:g}%", file=sys.stderr)
        return 1
    print(f"no regressions beyond {args.threshold:g}% "
          f"({len(imps)} improved)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
