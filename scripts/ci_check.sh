#!/usr/bin/env bash
# Repository CI gate — the fast, accelerator-free checks that keep the
# docs and the perf claims honest:
#
#   1. telemetry catalog sync: every registered dl4j_* metric is in
#      the README catalog (Observability / Diagnostics / Scaling
#      observatory sections) with the right type, and the catalog
#      documents nothing the code no longer registers
#      (scripts/check_telemetry_catalog.py);
#   2. bench regression gate: when at least two BENCH_r*.json rounds
#      are checked in, the newest must not regress any
#      known-polarity metric of the previous round by more than the
#      threshold — including the PR-9 `scaling` (efficiency up, skew
#      down) and `step_breakdown` (phase seconds down) blocks
#      (scripts/check_bench_regression.py);
#   3. fsdp residency gate: the ZeRO-3 bench leg on the virtual
#      8-device CPU mesh must measure per-chip param + updater-state
#      residency <= 1/4 of dense (the ISSUE 10 acceptance bar,
#      benchmarks/bench_fsdp.py);
#   4. chaos gate: a REAL SIGTERM mid-epoch in a subprocess must exit
#      75 after a final snapshot, and re-running the same command must
#      auto-resume onto the uninterrupted loss/parameter trajectory
#      with zero manual steps (the ISSUE 11 acceptance bar,
#      tests/test_chaos.py);
#   5. 2D equivalence gate: the (dp x tp) and (fsdp x tp) training
#      modes on the virtual 8-device mesh must track the dp-only dense
#      trajectory, keep the update exchange off the model axis, and
#      survive checkpoint/remesh back to 1D (the ISSUE 12 acceptance
#      bar, tests/test_2d_parallel.py);
#   6. kernel conformance gate: the Pallas conv/BN/ReLU epilogue
#      family must match the dense lowering bit-for-tolerance in
#      interpret mode (forward + gradients, incl. an f64
#      central-difference check) and every kernel family must
#      dispatch through the unified kernel-select ladder with
#      counted decisions (the ISSUE 13 acceptance bar,
#      tests/test_conv_pallas.py + tests/test_kernel_select.py). The
#      families' auto rung is XLA's lowering on every platform since
#      PR 33 (the chip's whole ResNet-50 step is 2.58x faster without
#      them, PERF.md section 6); the force rung (=1) keeps them
#      reachable and this gate keeps them honest until ROADMAP C4
#      deletes them;
#   7. layer-attribution conformance gate: per-layer flops/bytes on
#      LeNet + BERT-tiny must sum to the whole-model cost_analysis
#      within 1%, with the named-scope annotations actually reaching
#      the compiled HLO (the ISSUE 14 acceptance bar,
#      scripts/check_layer_attribution.py);
#   8. serving-SLO gate: a 2-replica router under concurrent load
#      across a live warm-then-drain rollout must answer every
#      request with a bitwise-correct 200 or a well-formed shed
#      (429/503 + integer Retry-After), drop nothing, and show zero
#      post-warmup retraces (the ISSUE 15 acceptance bar,
#      scripts/check_serving_slo.py);
#   9. generative conformance gate: paged-KV decode (Pallas kernel
#      forced, interpret mode) must be greedy-token-equal to the
#      dense full-re-forward reference, join/leave churn must never
#      retrace after warmup, and the KV pool must free every block
#      and reconcile with its dl4j_kv_pool_bytes gauge (the ISSUE 16
#      acceptance bar, scripts/check_generative.py);
#  10. request-tracing gate: one traced predict through the 2-replica
#      router must yield a connected span tree (every req.<phase>
#      span inside the request root, the root inside the router's
#      req.route envelope, durations consistent), echo the trace id
#      on the response with the latency-histogram exemplar carrying
#      it, and a forced shed storm must dump the request flight
#      recorder with per-phase timings (the ISSUE 17 acceptance bar,
#      scripts/check_request_tracing.py);
#  11. pipeline equivalence gate: pp=2 and pp2×dp on the virtual
#      8-device mesh must track the dp-only dense 4-step trajectory
#      (Sgd/Nesterovs/Adam, MLN + graph, both schedules), 1F1B must
#      hold strictly lower peak activation residency than GPipe at
#      equal n_micro, and pp checkpoints must restore onto a 1D mesh
#      (the ISSUE 18 acceptance bar, tests/test_pipeline.py);
#  12. static analysis gate: dl4j-lint (jit-purity, lock-discipline,
#      env-registry, metric-registry, spec-invariants) over the whole
#      tree must surface no finding outside the checked-in baseline,
#      and no rule's finding count may grow past its baselined count
#      (the ISSUE 19 acceptance bar, scripts/dl4j_lint);
#  13. encoded-rung equivalence-and-compression gate: the ENCODED
#      update exchange must train on the real fit path (loss
#      descends), exchange_report must show encoded_wire_bytes
#      strictly below the dense counterfactual, the live sparsity
#      gauge/wire counter/compression-ratio series must be populated,
#      and encoded ×tp on a 2D mesh must keep the compressed dp
#      exchange entirely off the model axis (the ISSUE 20 acceptance
#      bar, scripts/check_encoded.py).
#
# Usage: scripts/ci_check.sh [--threshold PCT]     (default 10)
# Exit 0 = all gates clean, 1 = a gate failed, 2 = bad usage.
set -u
cd "$(dirname "$0")/.."

THRESHOLD=10
while [ $# -gt 0 ]; do
  case "$1" in
    --threshold) THRESHOLD="$2"; shift 2 ;;
    *) echo "usage: $0 [--threshold PCT]" >&2; exit 2 ;;
  esac
done

fail=0

echo "== telemetry catalog sync =="
python scripts/check_telemetry_catalog.py || fail=1

echo "== bench regression gate =="
rounds=$(ls BENCH_r*.json 2>/dev/null | sort | tail -n 2)
n=$(printf '%s\n' "$rounds" | grep -c '[^[:space:]]')
if [ "$n" -lt 2 ]; then
  echo "fewer than two BENCH_r*.json rounds checked in; skipping"
else
  baseline=$(printf '%s\n' "$rounds" | head -n 1)
  fresh=$(printf '%s\n' "$rounds" | tail -n 1)
  echo "comparing $baseline -> $fresh (threshold ${THRESHOLD}%)"
  python scripts/check_bench_regression.py \
      --threshold "$THRESHOLD" "$baseline" "$fresh" || fail=1
fi

echo "== fsdp residency gate =="
fsdp_out=$(JAX_PLATFORMS=cpu python benchmarks/bench_fsdp.py) || fail=1
printf '%s\n' "$fsdp_out" | python -c '
import json, sys
lines = [l for l in sys.stdin if l.startswith("{")]
rec = json.loads(lines[-1]) if lines else {}
ok = rec.get("fsdp_resident_quarter_of_dense") is True
verdict = "OK" if ok else "FAIL: above 1/4 of dense"
ratio = rec.get("hbm_total_savings_ratio")
print(f"fsdp per-chip residency savings: {ratio}x ({verdict})")
sys.exit(0 if ok else 1)' || fail=1

echo "== chaos / auto-resume gate =="
JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q \
    -p no:cacheprovider || fail=1

echo "== 2D parallelism equivalence gate =="
JAX_PLATFORMS=cpu python -m pytest tests/test_2d_parallel.py -q \
    -p no:cacheprovider || fail=1

echo "== kernel conformance gate =="
JAX_PLATFORMS=cpu python -m pytest tests/test_conv_pallas.py \
    tests/test_kernel_select.py -q -p no:cacheprovider || fail=1

echo "== layer-attribution conformance gate =="
JAX_PLATFORMS=cpu python scripts/check_layer_attribution.py || fail=1

echo "== serving-SLO gate =="
JAX_PLATFORMS=cpu python scripts/check_serving_slo.py || fail=1

echo "== generative conformance gate =="
JAX_PLATFORMS=cpu python scripts/check_generative.py || fail=1

echo "== request-tracing gate =="
JAX_PLATFORMS=cpu python scripts/check_request_tracing.py || fail=1

echo "== pipeline equivalence gate =="
JAX_PLATFORMS=cpu python -m pytest tests/test_pipeline.py -q \
    -p no:cacheprovider || fail=1

echo "== static analysis gate =="
python -m scripts.dl4j_lint \
    --baseline scripts/dl4j_lint_baseline.json || fail=1

echo "== encoded-rung compression gate =="
JAX_PLATFORMS=cpu python scripts/check_encoded.py || fail=1

exit $fail
