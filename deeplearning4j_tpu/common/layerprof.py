"""Layer-level attribution observatory: per-layer time/flops/bytes.

The telemetry spine (PR 2) measures the process, diagnostics (PR 7)
the device, and the scaling observatory (PR 9) the step — but none of
them says **which layer** the headroom lives in.  This module closes
that gap on three legs:

1. **Annotation** — the fit funnels wrap every layer/vertex/op trace
   in :func:`scope`, which enters ``jax.named_scope("dl4j.<name>")``
   so the compiled HLO's per-instruction ``op_name`` metadata carries
   layer identity through forward (``jvp(dl4j.<name>)``) AND backward
   (``transpose(jvp(dl4j.<name>))``) — including the custom_vjp
   backward of the hand-written Pallas kernels, whose transpose rules
   inherit the enclosing scope.  ``scope`` also pushes onto a
   thread-local stack that :mod:`ops.kernel_select` reads at trace
   time, so every kernel-dispatch decision is attributed to the layer
   whose trace made it.  Annotations are metadata-only: steady-state
   step cost is ZERO (the context manager runs at trace time, never
   per executed step), which is how the layer rides the established
   <1% overhead budget with the gate default-ON.

2. **Static attribution** — :func:`attribute_compiled` partitions a
   compiled program's whole-model ``cost_analysis()`` flops/bytes by
   scope: the optimized HLO text is parsed per instruction (fusion
   interiors included — each fused instruction keeps its own
   metadata), an analytic cost model weighs every instruction (dot =
   2·out·k, conv = 2·out·window·Cin/g, elementwise = out elems,
   fusion boundary bytes at the call site), and the per-scope raw
   weights proportionally partition the XLA totals — so per-layer
   sums reconcile with the whole-model ``cost_analysis`` totals BY
   CONSTRUCTION (the CI gate re-checks it), while ``raw_model``
   reports the unscaled parser totals and their error vs XLA for
   honesty.

3. **Dynamic attribution** — :func:`attribute_trace` buckets
   device-op durations from a chrome trace (the PR-9
   ``ProfileCapture`` artifacts) by the same scope metadata into
   per-layer fwd/bwd milliseconds; :func:`join_dynamic` merges them
   into a static report and runs ``diagnostics.roofline`` per layer,
   so every fused-kernel claim reads "layer X moved from a% to b% of
   roof".  On CPU (where ``jax.profiler`` emits no scoped device
   ops) the bench leg falls back to sharing measured step time by the
   static roofline-time weights, marked ``time_source`` so proxy
   milliseconds are never mistaken for chip measurements.

Surfaces: ``model.layer_report()`` (MultiLayerNetwork /
ComputationGraph / Bert), ``GET /api/layers`` on the UIServer,
``dl4j_layer_seconds{layer,pass}`` + ``dl4j_layer_flops`` /
``dl4j_layer_bytes`` metrics, the ``layer_attribution`` bench block,
a ``top_layer`` field on flight-recorder step records, and the
``scripts/dl4j_layers.py`` CLI table.  Gate: ``DL4J_TPU_LAYERPROF``
(default on; ``Environment.extra["layerprof"]`` overrides, like the
kernel gates).
"""
from __future__ import annotations

import logging
import os
import re
import threading
from typing import Dict, List, Optional

import jax

from deeplearning4j_tpu.common import telemetry

log = logging.getLogger(__name__)

#: prefix all scope annotations carry inside HLO metadata
SCOPE_PREFIX = "dl4j."

#: the device whose ridge point weighs the static partition when the
#: present device has no row in ``diagnostics.DEVICE_PEAKS`` (the CPU
#: backend): a weighting for ``bound``/``est_ms``, never a peak — the
#: report says so and carries no percent-of-roof
WEIGHTING_DEVICE_KIND = "TPU v5 lite"

_layer_seconds = telemetry.histogram(
    "dl4j_layer_seconds",
    "per-layer device time from dynamic trace attribution, by layer "
    "scope and pass (fwd/bwd) — seconds per attributed capture")
_layer_flops = telemetry.gauge(
    "dl4j_layer_flops",
    "per-layer share of the compiled step's cost-analysis flops "
    "(static scope partition; refreshed per layer_report)")
_layer_bytes = telemetry.gauge(
    "dl4j_layer_bytes",
    "per-layer share of the compiled step's cost-analysis bytes "
    "accessed (static scope partition; refreshed per layer_report)")

_tls = threading.local()
_state_lock = threading.Lock()
_last_report: Optional[dict] = None
_top_layer: Optional[str] = None
#: trace-time kernel-decision join: scope -> kernel family -> decision
_decisions: Dict[str, Dict[str, dict]] = {}


# ----------------------------------------------------------------------
# gate + annotation
def enabled() -> bool:
    """The ``DL4J_TPU_LAYERPROF`` tri-state gate (default ON);
    ``Environment.extra["layerprof"]`` overrides the env var, like the
    kernel-select gates."""
    from deeplearning4j_tpu.common.environment import Environment
    flag = Environment.get().extra.get("layerprof")
    if flag is None:
        flag = os.environ.get("DL4J_TPU_LAYERPROF")
    if flag is None or str(flag) == "":
        return True
    return str(flag) in ("1", "true", "True", "yes")


class _NullScope:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullScope()

_SAFE_RE = re.compile(r"[^0-9A-Za-z_.]")


def sanitize(name: str) -> str:
    """Scope names must survive the HLO metadata round-trip: restrict
    to the characters the attribution regex can re-extract."""
    return _SAFE_RE.sub("_", str(name)) or "_"


class _Scope:
    """Trace-time layer annotation: pushes the name onto jax's name
    stack (HLO metadata) AND a thread-local stack (the kernel-select
    join).  Runs only while a program is being traced — never on the
    executed step path."""

    __slots__ = ("name", "_ns")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self.name)
        self._ns = jax.named_scope(SCOPE_PREFIX + self.name)
        self._ns.__enter__()
        return self

    def __exit__(self, *exc):
        try:
            return self._ns.__exit__(*exc)
        finally:
            _tls.stack.pop()


def scope(name: str):
    """Annotate the with-block as layer ``name`` (sanitized).  A
    no-op context when the gate is off."""
    if not enabled():
        return _NULL
    return _Scope(sanitize(name))


def current_scope() -> Optional[str]:
    """The innermost active :func:`scope` name on this thread (trace
    time only), or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


# ----------------------------------------------------------------------
# kernel-decision join (fed by ops.kernel_select.select at trace time)
def note_selection(selection) -> None:
    """Record a :class:`ops.kernel_select.Selection` against the layer
    scope whose trace made it."""
    sc = current_scope() or "_unscoped"
    with _state_lock:
        per = _decisions.setdefault(sc, {})
        prev = per.get(selection.kernel)
        if prev is None:
            per[selection.kernel] = {
                "kernel": selection.kernel,
                "fused": bool(selection.fused),
                "decision": selection.decision,
                "reason": selection.reason,
                "sites": 1,
            }
        else:
            prev.update(fused=bool(selection.fused),
                        decision=selection.decision,
                        reason=selection.reason)
            prev["sites"] += 1


def kernel_decisions(scope_name: Optional[str] = None) -> dict:
    """The recorded trace-time decisions: for one scope (``{kernel:
    decision}``) or all scopes when ``scope_name`` is None."""
    with _state_lock:
        if scope_name is not None:
            return {k: dict(v)
                    for k, v in _decisions.get(scope_name, {}).items()}
        return {s: {k: dict(v) for k, v in per.items()}
                for s, per in _decisions.items()}


def reset_decisions() -> None:
    with _state_lock:
        _decisions.clear()


# ----------------------------------------------------------------------
# HLO parsing: per-instruction analytic cost model keyed by scope
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_CALL_RE = re.compile(r"^(.*?)\s+([a-z][a-z0-9\-]*)\(")
_META_RE = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_SCOPE_META_RE = re.compile(r"dl4j\.([0-9A-Za-z_.]*[0-9A-Za-z_])")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_WINDOW_RE = re.compile(r"window=\{[^}]*size=([0-9x]+)")
_GROUPS_RE = re.compile(r"feature_group_count=([0-9]+)")
_DIMLABELS_RE = re.compile(r"dim_labels=([a-z0-9?]+)_([a-z0-9?]+)->")

#: ~1 flop per output element (the HloCostAnalysis convention for
#: simple elementwise math; comparisons/selects/copies count zero)
_ELEMENTWISE_FLOP = frozenset((
    "add", "subtract", "multiply", "divide", "remainder", "maximum",
    "minimum", "negate", "abs", "sign", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "clamp",
))
_TRANSCENDENTAL = frozenset((
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "tanh", "rsqrt", "sqrt", "cbrt", "power", "sine",
    "cosine", "tan", "atan2", "erf",
))
#: never materialized / free at runtime: no byte traffic of their own
_FREE_BYTES = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
    # control-flow shells: the work lives in the called computations
    "while", "conditional", "call",
))


def _shape_cost(text: str):
    """(elements, bytes) summed over every ``dtype[dims]`` shape token
    in ``text`` (tuple types contribute every component)."""
    elems = byts = 0
    for dt, dims in _SHAPE_RE.findall(text):
        unit = _DTYPE_BYTES.get(dt)
        if unit is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        byts += n * unit
    return elems, byts


def _shape_dims(text: str) -> Optional[List[int]]:
    """Dims of the FIRST shape token in ``text`` (an operand's array
    shape), or None."""
    m = _SHAPE_RE.search(text)
    if not m or m.group(1) not in _DTYPE_BYTES:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def _split_call(rest: str):
    """``rest`` starts at the call '('; returns (args, attrs) with
    balanced-paren scanning (metadata op_names contain parens, so a
    greedy regex would mis-split)."""
    depth = 0
    for j, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[1:j], rest[j + 1:]
    return rest[1:], ""


def _operand_bytes(args: str, symtab: Dict[str, tuple],
                   index: int) -> float:
    """Byte size of the ``index``-th operand of a call, from its
    inline shape when present or the computation symbol table."""
    toks, depth, start = [], 0, 0
    for j, ch in enumerate(args):
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        elif ch == "," and depth == 0:
            toks.append(args[start:j])
            start = j + 1
    toks.append(args[start:])
    tok = toks[index] if index < len(toks) else ""
    b = _shape_cost(tok)[1]
    if b:
        return float(b)
    m = _OPERAND_RE.search(tok)
    if m:
        ent = symtab.get(m.group(1))
        if ent:
            return float(ent[1])
    return 0.0


class _ScopeCost:
    __slots__ = ("flops_fwd", "flops_bwd", "bytes_fwd", "bytes_bwd",
                 "transcendentals")

    def __init__(self):
        self.flops_fwd = self.flops_bwd = 0.0
        self.bytes_fwd = self.bytes_bwd = 0.0
        self.transcendentals = 0.0


def _conv_flops(out_elems, args, attrs, symtab):
    """2 · out · window · Cin/groups — window from the textual window
    spec, Cin from dim_labels against the lhs operand shape."""
    win = 1
    m = _WINDOW_RE.search(attrs)
    if m:
        for d in m.group(1).split("x"):
            win *= int(d)
    groups = 1
    m = _GROUPS_RE.search(attrs)
    if m:
        groups = max(int(m.group(1)), 1)
    lhs_dims = _shape_dims(args)
    if lhs_dims is None:
        first = _OPERAND_RE.search(args)
        if first:
            lhs_dims = symtab.get(first.group(1), (None,))[0]
    in_feat = None
    m = _DIMLABELS_RE.search(attrs)
    if m and lhs_dims:
        fpos = m.group(1).find("f")
        if 0 <= fpos < len(lhs_dims):
            in_feat = lhs_dims[fpos]
    if in_feat is None and lhs_dims:
        in_feat = lhs_dims[-1]
    return 2.0 * out_elems * win * (in_feat or 1) / groups


def _dot_flops(out_elems, args, attrs, symtab):
    """2 · out · k, k = product of the lhs contracting dims."""
    m = _CDIMS_RE.search(attrs)
    cdims = [int(d) for d in m.group(1).split(",") if d] if m else []
    lhs_dims = _shape_dims(args)
    if lhs_dims is None:
        first = _OPERAND_RE.search(args)
        if first:
            lhs_dims = symtab.get(first.group(1), (None,))[0]
    k = 1
    if lhs_dims:
        for d in cdims:
            if d < len(lhs_dims):
                k *= lhs_dims[d]
    return 2.0 * out_elems * max(k, 1)


_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_CALLS_REF_RE = re.compile(r"calls=%([\w.\-]+)")
_WHILE_REF_RE = re.compile(
    r"condition=%([\w.\-]+), body=%([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%([\w.\-]+)")
_CONST_INT_RE = re.compile(r"constant\((\d+)\)")


def _comp_roles(hlo_text: str):
    """First pass over the HLO text: classify every computation by HOW
    it is called, since names alone lie (``region_*`` is both a
    scalar reduce applier — skip, its work is counted at the applying
    instruction — and a ``lax.scan`` while body — count, multiplied
    by the loop trip count).

    Returns ``{comp_name: execution-count multiplier}``: 0 for
    appliers/conditions, the trip count (times the parent's
    multiplier) for while bodies, the parent's multiplier for fusion
    interiors, 1 for ENTRY.  Trip counts come from the canonical cond
    pattern ``compare(counter, constant(N)), direction=LT``.

    Also computes per-fused-computation boundary bytes honestly:
    a parameter consumed only through ``dynamic-slice`` contributes
    the slice window, not the whole buffer (CPU scatter/sort loops
    index one row of a big table per trip), and a computation rooted
    at a ``dynamic-update-slice`` (in-placed by XLA) contributes the
    updated window instead of its full result."""
    parent: Dict[str, tuple] = {}   # comp -> (kind, parent_comp, trip)
    cond_trip: Dict[str, int] = {}
    body_cond: Dict[str, str] = {}  # while body -> its paired cond
    dus_root: Dict[str, float] = {}
    fusion_io: Dict[str, float] = {}  # comp -> touched parameter bytes
    entry = None
    current = None
    cur_const = None
    par_bytes: Dict[str, float] = {}
    par_slice: Dict[str, float] = {}
    par_full: set = set()

    def _finish_comp():
        if current is None:
            return
        touched = 0.0
        for pname, full in par_bytes.items():
            if pname in par_full or pname not in par_slice:
                touched += full
            else:
                touched += par_slice[pname]
        fusion_io[current] = touched

    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ("->" in stripped
                                       or stripped.startswith("ENTRY")):
            _finish_comp()
            m = _COMP_HEAD_RE.match(stripped)
            current = m.group(1) if m else None
            if stripped.startswith("ENTRY"):
                entry = current
            cur_const = None
            par_bytes, par_slice, par_full = {}, {}, set()
            continue
        m = _CONST_INT_RE.search(stripped)
        if m:
            cur_const = int(m.group(1))
        if "direction=LT" in stripped and current is not None and \
                cur_const is not None:
            cond_trip[current] = cur_const
        m = _WHILE_REF_RE.search(stripped)
        if m:
            parent.setdefault(m.group(1), ("cond", current, 0))
            parent.setdefault(m.group(2), ("body", current, 0))
            body_cond.setdefault(m.group(2), m.group(1))
        for name in _CALLS_REF_RE.findall(stripped):
            parent.setdefault(name, ("fusion", current, 0))
        for name in _TO_APPLY_RE.findall(stripped):
            parent.setdefault(name, ("applier", current, 0))

        hm = _HEAD_RE.match(stripped)
        call = _CALL_RE.match(hm.group(2)) if hm else None
        if not call:
            continue
        rtype, opcode = call.group(1), call.group(2)
        argstr, _ = _split_call(hm.group(2)[call.end() - 1:])
        if opcode == "parameter":
            par_bytes[hm.group(1)] = float(_shape_cost(rtype)[1])
            continue
        operands = _OPERAND_RE.findall(argstr)
        if opcode == "dynamic-update-slice" and \
                stripped.startswith("ROOT") and current is not None:
            ub = _operand_bytes(argstr, {}, 1)
            if ub:
                dus_root[current] = 2.0 * ub
            # the in-placed buffer (operand 0) is not copied: its
            # traffic is the window, already in dus_root
            for opn in operands[1:]:
                if opn in par_bytes:
                    par_full.add(opn)
            if operands and operands[0] in par_bytes:
                par_slice.setdefault(operands[0], 0.0)
            continue
        out_b = float(_shape_cost(rtype)[1])
        for j, opn in enumerate(operands):
            if opn not in par_bytes:
                continue
            if opcode == "dynamic-slice" and j == 0:
                par_slice[opn] = par_slice.get(opn, 0.0) + out_b
            else:
                par_full.add(opn)
    _finish_comp()

    mult: Dict[str, float] = {}

    def resolve(comp, depth=0):
        if comp in mult:
            return mult[comp]
        if comp == entry or comp not in parent or depth > 16:
            mult[comp] = 1.0
            return 1.0
        kind, par, _ = parent[comp]
        if kind in ("cond", "applier"):
            m = 0.0
        elif kind == "body":
            # trip from this body's paired cond (same while line);
            # fall back to 1 when the cond isn't the canonical
            # counter < constant pattern
            trip = cond_trip.get(body_cond.get(comp, ""), 1)
            m = trip * resolve(par, depth + 1)
        else:
            m = resolve(par, depth + 1)
        mult[comp] = m
        return m

    return parent, cond_trip, entry, resolve, dus_root, fusion_io


def parse_hlo(hlo_text: str) -> Dict[str, _ScopeCost]:
    """Walk the optimized-HLO text and accumulate the analytic cost
    model per ``dl4j.<scope>`` (``_unattributed`` collects un-scoped
    instructions).  Fusion interiors contribute flops under their own
    per-instruction metadata; the fusion call site contributes the
    boundary bytes under the fusion's (root) metadata.  While bodies
    (``lax.scan`` layers) are weighted by their loop trip count;
    reduce/scatter appliers and loop conditions are skipped — their
    work is counted at the applying instruction."""
    (parent, cond_trip, entry, resolve, dus_root,
     fusion_io) = _comp_roles(hlo_text)
    out: Dict[str, _ScopeCost] = {}
    in_fused = False
    factor = 1.0
    # per-computation symbol table: name -> (dims, bytes) — names are
    # only unique within a computation (every fused computation has a
    # %param_0)
    symtab: Dict[str, tuple] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and ("->" in stripped
                                       or stripped.startswith("ENTRY")):
            m = _COMP_HEAD_RE.match(stripped)
            comp = m.group(1) if m else None
            kind = parent.get(comp, (None,))[0]
            in_fused = kind == "fusion"
            factor = resolve(comp) if comp is not None else 1.0
            symtab = {}
            continue
        if factor == 0.0:
            continue
        m = _HEAD_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        call = _CALL_RE.match(rhs)
        if not call:
            continue
        result_type, opcode = call.group(1), call.group(2)
        args, attrs = _split_call(rhs[call.end() - 1:])
        out_elems, out_bytes = _shape_cost(result_type)
        symtab[name] = (_shape_dims(result_type), out_bytes)
        meta = _META_RE.search(attrs)
        op_name = meta.group(1) if meta else ""
        sm = _SCOPE_META_RE.search(op_name)
        scope_name = sm.group(1) if sm else "_unattributed"
        is_bwd = "transpose(" in op_name
        cost = out.get(scope_name)
        if cost is None:
            cost = out[scope_name] = _ScopeCost()

        flops = 0.0
        if opcode == "dot":
            flops = _dot_flops(out_elems, args, attrs, symtab)
        elif opcode == "convolution":
            flops = _conv_flops(out_elems, args, attrs, symtab)
        elif opcode in ("reduce", "reduce-window"):
            in_elems = _shape_cost(args)[0]
            if in_elems == 0:
                first = _OPERAND_RE.search(args)
                if first:
                    dims = symtab.get(first.group(1), (None,))[0]
                    if dims:
                        in_elems = 1
                        for d in dims:
                            in_elems *= d
            flops = float(max(in_elems, out_elems))
        elif opcode in _ELEMENTWISE_FLOP:
            flops = float(out_elems)
        elif opcode in _TRANSCENDENTAL:
            cost.transcendentals += float(out_elems) * factor
        if is_bwd:
            cost.flops_bwd += flops * factor
        else:
            cost.flops_fwd += flops * factor

        if in_fused or opcode in _FREE_BYTES:
            continue
        if opcode == "dynamic-update-slice":
            # only the updated window is touched (read update + write
            # region), not the full buffer — charging result+operands
            # would overcount scan carries by the carry size per step
            upd = _operand_bytes(args, symtab, index=1)
            op_bytes = 2.0 * (upd if upd else float(out_bytes))
        elif opcode == "dynamic-slice":
            op_bytes = 2.0 * float(out_bytes)   # read + write the slice
        elif opcode == "fusion":
            called = _CALLS_REF_RE.search(attrs)
            tgt = called.group(1) if called else None
            if tgt in fusion_io:
                # boundary bytes from the interior's actual access
                # pattern: dynamic-sliced params count their window,
                # a DUS root counts the updated window, not the full
                # in-placed buffer
                op_bytes = fusion_io[tgt] + (
                    dus_root[tgt] if tgt in dus_root
                    else float(out_bytes))
                if is_bwd:
                    cost.bytes_bwd += op_bytes * factor
                else:
                    cost.bytes_fwd += op_bytes * factor
                continue
            op_bytes = float(out_bytes)
            inline_b = _shape_cost(args)[1]
            if inline_b:
                op_bytes += inline_b
            else:
                for opn in _OPERAND_RE.findall(args):
                    ent = symtab.get(opn)
                    if ent:
                        op_bytes += ent[1]
        else:
            op_bytes = float(out_bytes)
            inline_b = _shape_cost(args)[1]
            if inline_b:
                op_bytes += inline_b
            else:
                for opn in _OPERAND_RE.findall(args):
                    ent = symtab.get(opn)
                    if ent:
                        op_bytes += ent[1]
        if is_bwd:
            cost.bytes_bwd += op_bytes * factor
        else:
            cost.bytes_fwd += op_bytes * factor
    return out


# ----------------------------------------------------------------------
# static attribution: partition cost_analysis totals by scope
def attribute_compiled(compiled, *, model_name: Optional[str] = None,
                       layer_types: Optional[dict] = None,
                       peak_tflops: Optional[float] = None,
                       peak_hbm_gbps: Optional[float] = None) -> dict:
    """Partition ``compiled.cost_analysis()`` flops/bytes by layer
    scope (see module docstring).  Returns the layer report and
    publishes it as the module's last report (``/api/layers``,
    ``top_layer``, the ``dl4j_layer_*`` gauges)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    total_flops = float(ca.get("flops", 0.0) or 0.0)
    total_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    raw = parse_hlo(compiled.as_text())

    raw_flops = sum(c.flops_fwd + c.flops_bwd for c in raw.values())
    raw_bytes = sum(c.bytes_fwd + c.bytes_bwd for c in raw.values())
    sf = (total_flops / raw_flops) if raw_flops else 0.0
    sb = (total_bytes / raw_bytes) if raw_bytes else 0.0

    from deeplearning4j_tpu.common import diagnostics
    kind = jax.devices()[0].device_kind
    row = diagnostics.device_peaks(kind)
    if peak_tflops and peak_hbm_gbps:
        peaks = {"tflops": peak_tflops, "hbm_gbps": peak_hbm_gbps,
                 "basis": "caller-supplied peaks"}
    elif row:
        peaks = {"tflops": row["tflops"], "hbm_gbps": row["hbm_gbps"],
                 "basis": f"DEVICE_PEAKS[{kind!r}]: {row['source']}"}
    else:
        row = diagnostics.DEVICE_PEAKS[WEIGHTING_DEVICE_KIND]
        peaks = {"tflops": row["tflops"], "hbm_gbps": row["hbm_gbps"],
                 "weighting_only": True,
                 "basis": f"device {kind!r} is not in DEVICE_PEAKS: "
                          f"the {WEIGHTING_DEVICE_KIND!r} ridge weighs "
                          "the static partition (bound, est_ms) and "
                          "no percent-of-roof is reported"}
    peak_tf, peak_bw = peaks["tflops"], peaks["hbm_gbps"]
    ridge = peak_tf * 1e12 / (peak_bw * 1e9)

    layers = {}
    attr_flops = attr_bytes = 0.0
    for name, c in raw.items():
        f_fwd, f_bwd = c.flops_fwd * sf, c.flops_bwd * sf
        b_fwd, b_bwd = c.bytes_fwd * sb, c.bytes_bwd * sb
        flops, byts = f_fwd + f_bwd, b_fwd + b_bwd
        ai = flops / max(byts, 1.0)
        est_s = max(flops / (peak_tf * 1e12), byts / (peak_bw * 1e9))
        ent = {
            "flops": round(flops),
            "bytes": round(byts),
            "flops_fwd": round(f_fwd), "flops_bwd": round(f_bwd),
            "bytes_fwd": round(b_fwd), "bytes_bwd": round(b_bwd),
            "share_flops": round(flops / total_flops, 4)
            if total_flops else 0.0,
            "share_bytes": round(byts / total_bytes, 4)
            if total_bytes else 0.0,
            "arithmetic_intensity": round(ai, 2),
            "bound": "compute" if ai >= ridge else "hbm",
            "est_ms": round(est_s * 1e3, 7),
        }
        if layer_types and name in layer_types:
            ent["type"] = layer_types[name]
        kd = kernel_decisions(name)
        if kd:
            ent["kernel"] = kd
        if name != "_unattributed":
            attr_flops += flops
            attr_bytes += byts
        layers[name] = ent

    # display/report order: heaviest first (ISSUE: "top-k by time")
    layers = dict(sorted(
        layers.items(),
        key=lambda kv: kv[1]["est_ms"], reverse=True))

    report = {
        "model": model_name,
        "peaks": peaks,
        "totals": {
            "flops": total_flops,
            "bytes": total_bytes,
            "transcendentals": float(
                ca.get("transcendentals", 0.0) or 0.0),
        },
        "raw_model": {
            "flops": round(raw_flops),
            "bytes": round(raw_bytes),
            "flops_err_pct": round(
                100.0 * (raw_flops - total_flops)
                / total_flops, 1) if total_flops else None,
            "bytes_err_pct": round(
                100.0 * (raw_bytes - total_bytes)
                / total_bytes, 1) if total_bytes else None,
            # positive err is expected on scan models: the analytic
            # model weighs while bodies by their trip count (executed
            # work), XLA's cost_analysis counts loop bodies once
            "loop_semantics": "executed-trips",
        },
        "coverage": {
            "flops": round(attr_flops / total_flops, 4)
            if total_flops else 0.0,
            "bytes": round(attr_bytes / total_bytes, 4)
            if total_bytes else 0.0,
        },
        "time_source": "static_roofline_model",
        "layers": layers,
    }
    _publish(report)
    return report


def reconcile_error_pct(report: dict) -> float:
    """Max relative error (percent) between the per-layer sums and the
    whole-model totals — the CI conformance gate's number.  ~0 by
    construction; a parser regression shows up here."""
    worst = 0.0
    for key in ("flops", "bytes"):
        total = report["totals"][key]
        if not total:
            continue
        got = sum(ent[key] for ent in report["layers"].values())
        worst = max(worst, abs(got - total) / total * 100.0)
    return worst


# ----------------------------------------------------------------------
# dynamic attribution: trace events -> per-layer fwd/bwd milliseconds
def attribute_trace(events) -> Dict[str, dict]:
    """Bucket chrome-trace complete events carrying ``dl4j.<scope>``
    metadata (event name or args) into per-scope
    ``{"fwd_ms", "bwd_ms"}``; observes ``dl4j_layer_seconds``."""
    out: Dict[str, dict] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        hay = str(ev.get("name", ""))
        args = ev.get("args")
        if isinstance(args, dict):
            for v in args.values():
                if isinstance(v, str) and "dl4j." in v:
                    hay = hay + " " + v
        m = _SCOPE_META_RE.search(hay)
        if not m:
            continue
        p = "bwd" if "transpose(" in hay else "fwd"
        d = out.setdefault(m.group(1), {"fwd_ms": 0.0, "bwd_ms": 0.0})
        d[p + "_ms"] += float(ev.get("dur", 0) or 0) / 1e3
    for scope_name, d in out.items():
        for p in ("fwd", "bwd"):
            if d[p + "_ms"]:
                _layer_seconds.observe(
                    d[p + "_ms"] / 1e3,
                    **{"layer": scope_name, "pass": p})
    return out


def attribute_trace_file(path: str) -> Dict[str, dict]:
    """:func:`attribute_trace` over a chrome-trace file (a
    ``ProfileCapture`` artifact; ``.gz`` handled)."""
    from deeplearning4j_tpu.common.telemetry import _load_trace
    return attribute_trace(_load_trace(path).get("traceEvents", []))


def join_dynamic(report: dict, layer_ms: Dict[str, dict],
                 time_source: str = "trace") -> dict:
    """Merge measured per-layer milliseconds into a static report and
    re-run the roofline per layer against the measured time — the
    join that turns "kernel X fused" into "layer X moved from a% to
    b% of roof"."""
    from deeplearning4j_tpu.common import diagnostics
    peaks = report.get("peaks", {})
    if peaks.get("weighting_only"):
        peaks = {}                  # no peak for this device: no percent
    for name, ent in report["layers"].items():
        ms = layer_ms.get(name)
        if not ms:
            continue
        ent["fwd_ms"] = round(ms.get("fwd_ms", 0.0), 4)
        ent["bwd_ms"] = round(ms.get("bwd_ms", 0.0), 4)
        total_s = (ent["fwd_ms"] + ent["bwd_ms"]) / 1e3
        if total_s > 0:
            rl = diagnostics.roofline(
                ent["flops"], ent["bytes"], total_s,
                peak_tflops=peaks.get("tflops"),
                peak_hbm_gbps=peaks.get("hbm_gbps"))
            ent["pct_of_roof"] = rl.get("pct_of_roof")
            ent["tflops"] = rl.get("tflops")
    report["time_source"] = time_source
    _publish(report)
    return report


def share_step_time(report: dict, step_ms: float,
                    time_source: str = "static_share_proxy"
                    ) -> Dict[str, dict]:
    """CPU-proxy fallback: split a measured whole-step wall time into
    per-layer fwd/bwd milliseconds by the static roofline-time
    weights.  Honest about what it is (``time_source`` marks it) —
    the chip path uses :func:`attribute_trace` on real device ops."""
    layers = report["layers"]
    est_total = sum(e["est_ms"] for e in layers.values()) or 1.0
    out = {}
    for name, ent in layers.items():
        ms = step_ms * ent["est_ms"] / est_total
        denom = max(ent["flops_fwd"] + ent["flops_bwd"]
                    + ent["bytes_fwd"] + ent["bytes_bwd"], 1.0)
        fwd_w = (ent["flops_fwd"] + ent["bytes_fwd"]) / denom
        out[name] = {"fwd_ms": ms * fwd_w, "bwd_ms": ms * (1 - fwd_w)}
    join_dynamic(report, out, time_source=time_source)
    return out


# ----------------------------------------------------------------------
# module report state (UIServer / flight recorder / CLI read this)
def _publish(report: dict) -> None:
    global _last_report, _top_layer
    top = None
    best = -1.0
    for name, ent in report["layers"].items():
        if name == "_unattributed":
            continue
        t = ent.get("fwd_ms", 0.0) + ent.get("bwd_ms", 0.0) \
            or ent.get("est_ms", 0.0)
        if t > best:
            best, top = t, name
        _layer_flops.set(ent["flops"], layer=name)
        _layer_bytes.set(ent["bytes"], layer=name)
    with _state_lock:
        _last_report = report
        _top_layer = top


def last_report() -> Optional[dict]:
    """The most recent layer report computed in this process."""
    with _state_lock:
        return _last_report


def top_layer() -> Optional[str]:
    """The heaviest layer of the last report (measured time when the
    dynamic join ran, else the static roofline-time estimate) — the
    flight recorder stamps this onto every step record."""
    return _top_layer


def reset() -> None:
    """Test hook: clear report state and the decision join."""
    global _last_report, _top_layer
    with _state_lock:
        _last_report = None
        _top_layer = None
        _decisions.clear()
