"""Global runtime flags facade.

Reference parity: ``org.nd4j.linalg.factory.Nd4j.getEnvironment()`` backed by
libnd4j's native ``Environment`` (include/system/Environment.h) plus the
``ND4JSystemProperties`` / ``ND4JEnvironmentVars`` flag surface (SURVEY.md
section 5.6). On TPU the native knobs become XLA/libtpu options; this facade
keeps one place for debug/verbose/profiling toggles and maps what it can onto
jax config.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field


@dataclass
class _Env:
    debug: bool = False
    verbose: bool = False
    profiling: bool = False
    check_for_nan: bool = False
    check_for_inf: bool = False
    allow_helpers: bool = True          # reference: cuDNN/oneDNN enablement
    default_float_dtype: str = "float32"
    # TPU-specific: matmul precision for f32 ops ('default'|'high'|'highest')
    matmul_precision: str = "default"
    # device-side input staging (datasets.prefetch.DevicePrefetcher):
    # fit() wraps iterators so the H2D copy of batch n+1 overlaps the
    # device step on batch n. Depth 2 = classic double buffering.
    device_prefetch: bool = True
    device_prefetch_depth: int = 2
    # warn after this many distinct compiled input signatures per
    # network (shape churn -> retrace storm; pad or bucket instead)
    retrace_warn_threshold: int = 5
    # unified telemetry spine (common.telemetry): metrics registry +
    # chrome-trace spans across train/infer/ETL; /metrics on UIServer
    telemetry: bool = True
    # ZeRO-1 cross-replica sharded weight update (parallel.zero): on a
    # dp>1 mesh the updater + its state run on a 1/N parameter shard
    # per replica instead of fully replicated. 0 restores the dense
    # replicated update exactly.
    sharded_update: bool = True
    # full FSDP / ZeRO-3 (parallel.zero): params + grads resident 1/N
    # per replica with per-layer just-in-time all-gather. 0 demotes
    # update_exchange="fsdp" requests to the ZeRO-1 sharded update.
    # fsdp_prefetch additionally emits layer k+1's gather while layer
    # k computes (off -> strictly on-demand gathers).
    fsdp: bool = True
    fsdp_prefetch: bool = True
    # encoded update exchange (parallel.zero / parallel.encoding): the
    # compressed-collective fourth rung (threshold sign·tau, int8,
    # 1-bit) with error-feedback residuals. 0 demotes
    # update_exchange="encoded" requests to the ZeRO-1 sharded update
    # (the exchange survives, only the compression drops).
    encoded_update: bool = True
    # numerics watchdog (common.diagnostics): opt-in sampled non-finite
    # check on loss / global grad norm inside the fit funnels; a trip
    # raises a structured NumericsEvent instead of training on NaNs
    numerics_watchdog: bool = False
    numerics_sample: int = 1            # check every Nth step
    # flight recorder (common.diagnostics): bounded ring of per-step
    # records, dumped to JSONL + chrome trace on crash/SIGTERM/watchdog
    flight_recorder: bool = True
    flight_recorder_steps: int = 256    # ring capacity (last N steps)
    flight_recorder_dir: str = "flightrec"  # dump dir (created on dump)
    flight_recorder_keep: int = 8       # newest K dumps retained
    # refresh HBM gauges from jax device memory stats every Nth
    # recorded step (the stats call is cheap but not free)
    hbm_sample_steps: int = 16
    # fault tolerance (common.faults): supervised in-process retries
    # after a training failure — attempts before giving up, and the
    # base of the capped exponential backoff between them (seconds)
    resume_retries: int = 3
    resume_backoff: float = 1.0
    # truly-async checkpoint snapshots (utils.checkpoint): fork a
    # donation-safe ON-DEVICE copy on the step path and defer the
    # device->host transfer to the background checkpoint writer. 0
    # restores the eager (step-loop-blocking) device_get.
    async_snapshot: bool = True
    # scaling observatory (common.stepstats): per-step phase
    # decomposition + cross-host straggler detection
    stepstats: bool = True
    straggler_factor: float = 2.0       # trip: worker > factor x mean
    straggler_min_step: float = 1e-3    # no trips below this mean step
    extra: dict = field(default_factory=dict)

    def set_debug(self, v: bool):
        self.debug = bool(v)

    def set_verbose(self, v: bool):
        self.verbose = bool(v)

    def set_profiling(self, v: bool):
        self.profiling = bool(v)


class Environment:
    """Process-wide singleton, env-var seeded.

    Env vars (analogue of ND4JEnvironmentVars):
      DL4J_TPU_DEBUG, DL4J_TPU_VERBOSE, DL4J_TPU_PROFILING,
      DL4J_TPU_CHECK_NAN, DL4J_TPU_CHECK_INF, DL4J_TPU_ALLOW_HELPERS,
      DL4J_TPU_DEVICE_PREFETCH, DL4J_TPU_DEVICE_PREFETCH_DEPTH,
      DL4J_TPU_RETRACE_WARN, DL4J_TPU_TELEMETRY,
      DL4J_TPU_SHARDED_UPDATE, DL4J_TPU_FSDP,
      DL4J_TPU_FSDP_PREFETCH, DL4J_TPU_ENCODED_UPDATE,
      DL4J_TPU_NUMERICS_WATCHDOG,
      DL4J_TPU_NUMERICS_SAMPLE, DL4J_TPU_FLIGHT_RECORDER,
      DL4J_TPU_FLIGHT_RECORDER_STEPS, DL4J_TPU_FLIGHT_RECORDER_DIR,
      DL4J_TPU_FLIGHT_RECORDER_KEEP, DL4J_TPU_HBM_SAMPLE_STEPS,
      DL4J_TPU_STEPSTATS, DL4J_TPU_STRAGGLER_FACTOR,
      DL4J_TPU_STRAGGLER_MIN_STEP, DL4J_TPU_RESUME_RETRIES,
      DL4J_TPU_RESUME_BACKOFF, DL4J_TPU_ASYNC_SNAPSHOT

    Read live (not cached here) by their subsystems:
      DL4J_TPU_COMPILE_CACHE (common.compilecache, read once at
      package import: =0 places no persistent XLA compilation cache,
      =1 places one even in a process pinned to the cpu platform;
      unset = on unless pinned to cpu. The directory is
      <checkout>/.jax_cache, or wherever jax's own
      JAX_COMPILATION_CACHE_DIR says — then this package sets none),
      DL4J_TPU_GRAPHOPT (post-import GraphOptimizer pipeline, default
      on; =0 kills), DL4J_TPU_DUMP_GRAPHOPT (op-walk dumps around
      each mutating pass), DL4J_TPU_FLASH_ATTENTION (tri-state: =1
      forces the Pallas flash sdpa backend, =0 kills it, unset =
      auto heuristic), DL4J_TPU_FUSED_BN_BWD (fused BN backward:
      unset = XLA's lowering on every platform, the chip's whole
      ResNet-50 step being 2.58x faster that way, PERF.md section 6
      PR 33; =1 forces the kernels anywhere, =0 kills),
      DL4J_TPU_FUSED_CONV (tri-state like the flash gate: the Pallas
      conv/BN/ReLU epilogue family — conv-bias-act, BN statistics +
      normalize, matmul+epilogue for aligned 1x1 convs; unset = XLA's
      lowering on every platform, same reading),
      DL4J_TPU_PAGED_ATTENTION (tri-state: the paged decode-attention
      Pallas kernel for the serving KV pool), DL4J_TPU_SSM_STATE
      (tri-state: the in-place recurrent-state update kernel for the
      serving state pool), DL4J_TPU_MOE_GROUPED (tri-state: the Pallas
      grouped matrix product of the served expert layer, ops/moe.py;
      all six gates resolve
      through the ops/kernel_select.py ladder: structural gate, then
      force/kill, then auto heuristic, every decision counted in
      dl4j_kernel_select_total),
      DL4J_TPU_CHAOS (common.faults fault injection: comma-separated
      kill_after_steps=N / hard_kill_after_steps=N /
      slow_worker=SECONDS / torn_checkpoint=1),
      DL4J_TPU_LAYERPROF (common.layerprof layer-attribution scopes:
      default on — the annotations are trace-time-only metadata with
      zero steady-state step cost; =0 kills them;
      Environment.extra["layerprof"] overrides the env var),
      DL4J_TPU_REQUEST_TRACE (common.tracectx per-request serving
      spans + exemplars: default on; =0 kills — request trace ids
      still mint so responses/logs stay joinable),
      DL4J_TPU_ACCESS_LOG / DL4J_TPU_ACCESS_LOG_SAMPLE (httputil
      sampled JSONL access log: path turns it on, sample rate keeps
      a deterministic 1-in-N slice),
      DL4J_TPU_REQREC / DL4J_TPU_REQREC_CAPACITY /
      DL4J_TPU_REQREC_DIR / DL4J_TPU_REQREC_SHED_THRESHOLD /
      DL4J_TPU_REQREC_SHED_WINDOW_S /
      DL4J_TPU_REQREC_STORM_COOLDOWN_S (serving.reqrec request
      flight recorder: default on, 512-record ring, dump dir falls
      back to DL4J_TPU_FLIGHT_RECORDER_DIR; storm = threshold sheds
      inside the window, then a cooldown between dumps),
      DL4J_TPU_SLO_TARGET / DL4J_TPU_SLO_FAST_S / DL4J_TPU_SLO_SLOW_S
      (serving.slo error-budget accounting: in-SLO target fraction,
      default 0.99, over fast/slow burn-rate windows, default
      300 s / 3600 s),
      DL4J_TPU_HTTP_HOST (bind interface for every HTTP server —
      httputil, ui.server, serving.router; default 127.0.0.1,
      loopback only; set 0.0.0.0 to expose beyond the host),
      DL4J_TPU_OBSERVATORY_PORT (parallel.sharedtraining leader port
      for the cross-worker step-stats aggregator, default 9470),
      DL4J_TPU_TELEMETRY_MAX_EVENTS (common.telemetry trace-event
      ring capacity, default 200000),
      DL4J_TPU_STEPSTATS_STEPS (common.stepstats per-step ring size,
      default 1024),
      DL4J_TPU_DATA_DIR (datasets: directory holding real iris.csv /
      MNIST IDX files; synthetic fallbacks are used when unset),
      DL4J_TPU_NATIVE_LIB (native.bridge: explicit path to the
      compiled helper library — load-or-fail, no silent fallback;
      the sanitizer suite points it at the ASan+UBSan build),
      DL4J_TPU_DISABLE_NATIVE (=1 forces the pure-Python fallbacks
      even when the native library is buildable),
      DL4J_TPU_TEST_PLATFORM (tests/benchmarks only: platform pin
      for the suite — default cpu with an 8-device virtual mesh;
      =tpu runs against the real accelerator),
      DL4J_TPU_ENCODED_SCHEME (parallel.encoding: default wire codec
      for update_exchange="encoded" when no EncodingSpec is passed —
      threshold | int8 | 1bit, default threshold),
      DL4J_TPU_KV_DTYPE (serving.batcher: KV-block pool dtype for
      generative serving — float32 | bfloat16, default float32; a
      per-model generate={"kv_dtype": ...} overrides it),
      DL4J_TPU_SERVING_PARAM_DTYPE (serving.registry: default
      register(param_dtype=...) low-precision residency cast for
      sharded/fsdp-resident serving params — bf16 | int8, unset =
      full precision)
    """

    _inst: _Env | None = None
    _lock = threading.Lock()

    @classmethod
    def get(cls) -> _Env:
        inst = cls._inst
        if inst is not None:    # lock-free fast path (ops call this per-op)
            return inst
        with cls._lock:
            if cls._inst is None:
                def b(name, dflt=False):
                    return os.environ.get(name, str(int(dflt))) in (
                        "1", "true", "True", "yes")
                cls._inst = _Env(
                    debug=b("DL4J_TPU_DEBUG"),
                    verbose=b("DL4J_TPU_VERBOSE"),
                    profiling=b("DL4J_TPU_PROFILING"),
                    check_for_nan=b("DL4J_TPU_CHECK_NAN"),
                    check_for_inf=b("DL4J_TPU_CHECK_INF"),
                    allow_helpers=b("DL4J_TPU_ALLOW_HELPERS", True),
                    device_prefetch=b("DL4J_TPU_DEVICE_PREFETCH", True),
                    device_prefetch_depth=int(os.environ.get(
                        "DL4J_TPU_DEVICE_PREFETCH_DEPTH", "2")),
                    retrace_warn_threshold=int(os.environ.get(
                        "DL4J_TPU_RETRACE_WARN", "5")),
                    telemetry=b("DL4J_TPU_TELEMETRY", True),
                    sharded_update=b("DL4J_TPU_SHARDED_UPDATE", True),
                    fsdp=b("DL4J_TPU_FSDP", True),
                    fsdp_prefetch=b("DL4J_TPU_FSDP_PREFETCH", True),
                    encoded_update=b("DL4J_TPU_ENCODED_UPDATE", True),
                    numerics_watchdog=b("DL4J_TPU_NUMERICS_WATCHDOG"),
                    numerics_sample=int(os.environ.get(
                        "DL4J_TPU_NUMERICS_SAMPLE", "1")),
                    flight_recorder=b("DL4J_TPU_FLIGHT_RECORDER", True),
                    flight_recorder_steps=int(os.environ.get(
                        "DL4J_TPU_FLIGHT_RECORDER_STEPS", "256")),
                    flight_recorder_dir=os.environ.get(
                        "DL4J_TPU_FLIGHT_RECORDER_DIR", "flightrec"),
                    flight_recorder_keep=int(os.environ.get(
                        "DL4J_TPU_FLIGHT_RECORDER_KEEP", "8")),
                    hbm_sample_steps=int(os.environ.get(
                        "DL4J_TPU_HBM_SAMPLE_STEPS", "16")),
                    resume_retries=int(os.environ.get(
                        "DL4J_TPU_RESUME_RETRIES", "3")),
                    resume_backoff=float(os.environ.get(
                        "DL4J_TPU_RESUME_BACKOFF", "1.0")),
                    async_snapshot=b("DL4J_TPU_ASYNC_SNAPSHOT", True),
                    stepstats=b("DL4J_TPU_STEPSTATS", True),
                    straggler_factor=float(os.environ.get(
                        "DL4J_TPU_STRAGGLER_FACTOR", "2.0")),
                    straggler_min_step=float(os.environ.get(
                        "DL4J_TPU_STRAGGLER_MIN_STEP", "1e-3")),
                )
            return cls._inst

    @classmethod
    def reset(cls):
        with cls._lock:
            cls._inst = None
