"""SameDiff-equivalent graph builder + executor.

Reference parity: ``org.nd4j.autodiff.samediff.SameDiff`` / ``SDVariable``
(SURVEY.md S1), autodiff (S2), sessions (S3), fit (S4), save/load (S5).
Call-stack parity: `SameDiff.output()` / `.fit()` (SURVEY.md §3.3).

TPU-first: the op DAG is evaluated by ONE traced-and-jitted function per
(outputs, placeholder-signature) — XLA sees the whole graph and fuses
it; `jax.value_and_grad` over that trace replaces the reference's
reverse-topo `doDiff` backward-graph construction; sessions/dependency
tracking/memory managers are unnecessary (XLA owns scheduling+memory).
"""
from __future__ import annotations

import enum
import io
import json
import logging
import zipfile
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger("deeplearning4j_tpu")

from deeplearning4j_tpu.autodiff.registry import get_op
from deeplearning4j_tpu.common import layerprof

# ops that consume a PRNG key at execution time; the executor folds a
# per-op key out of the step rng (deterministic per op position)
RNG_OPS = {"dropout", "random_normal", "random_uniform",
           "random_bernoulli"}


class VariableType(enum.Enum):
    """Reference: org.nd4j.autodiff.samediff.VariableType."""
    VARIABLE = "VARIABLE"          # trainable
    CONSTANT = "CONSTANT"
    PLACEHOLDER = "PLACEHOLDER"
    ARRAY = "ARRAY"                # op output


class SDVariable:
    """Symbolic handle into a SameDiff graph (reference: SDVariable).
    Operator overloads build graph nodes; `.eval()` executes."""

    def __init__(self, sd: "SameDiff", name: str, var_type: VariableType,
                 shape=None, dtype=None):
        self.sd = sd
        self.name = name
        self.var_type = var_type
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype

    # -- graph-building sugar ------------------------------------------
    def _bin(self, other, op):
        other = self.sd._as_var(other)
        return self.sd._op(op, [self, other])

    def __add__(self, o):
        return self._bin(o, "add")

    def __radd__(self, o):
        return self.sd._as_var(o)._bin(self, "add")

    def __sub__(self, o):
        return self._bin(o, "sub")

    def __rsub__(self, o):
        return self.sd._as_var(o)._bin(self, "sub")

    def __mul__(self, o):
        return self._bin(o, "mul")

    def __rmul__(self, o):
        return self.sd._as_var(o)._bin(self, "mul")

    def __truediv__(self, o):
        return self._bin(o, "div")

    def __rtruediv__(self, o):
        return self.sd._as_var(o)._bin(self, "div")

    def __pow__(self, o):
        return self._bin(o, "pow")

    def __matmul__(self, o):
        return self._bin(o, "matmul")

    def __neg__(self):
        return self.sd._op("neg", [self])

    def __gt__(self, o):
        return self._bin(o, "gt")

    def __ge__(self, o):
        return self._bin(o, "gte")

    def __lt__(self, o):
        return self._bin(o, "lt")

    def __le__(self, o):
        return self._bin(o, "lte")

    # -- named methods (reference SDVariable surface) ------------------
    def add(self, o):
        return self.__add__(o)

    def sub(self, o):
        return self.__sub__(o)

    def mul(self, o):
        return self.__mul__(o)

    def div(self, o):
        return self.__truediv__(o)

    def rdiv(self, o):
        return self._bin(o, "rdiv")

    def mmul(self, o):
        return self._bin(o, "matmul")

    def dot(self, o):
        return self._bin(o, "dot")

    def sum(self, axis=None, keep_dims=False):
        return self.sd._op("reduce_sum", [self],
                           {"axis": axis, "keep_dims": keep_dims})

    def mean(self, axis=None, keep_dims=False):
        return self.sd._op("reduce_mean", [self],
                           {"axis": axis, "keep_dims": keep_dims})

    def max(self, axis=None, keep_dims=False):
        return self.sd._op("reduce_max", [self],
                           {"axis": axis, "keep_dims": keep_dims})

    def min(self, axis=None, keep_dims=False):
        return self.sd._op("reduce_min", [self],
                           {"axis": axis, "keep_dims": keep_dims})

    def std(self, axis=None, keep_dims=False):
        return self.sd._op("reduce_std", [self],
                           {"axis": axis, "keep_dims": keep_dims})

    def norm2(self, axis=None):
        return self.sd._op("reduce_norm2", [self], {"axis": axis})

    def argmax(self, axis=-1):
        return self.sd._op("argmax", [self], {"axis": axis})

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        return self.sd._op("reshape", [self], {"shape": list(shape)})

    def permute(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return self.sd._op("permute", [self], {"axes": list(axes)})

    def transpose(self):
        return self.sd._op("permute", [self], {"axes": [1, 0]})

    def cast(self, dtype):
        return self.sd._op("cast", [self], {"dtype": str(dtype)})

    def rename(self, new_name: str) -> "SDVariable":
        self.sd._rename(self.name, new_name)
        self.name = new_name
        return self

    # -- execution -----------------------------------------------------
    def eval(self, placeholders: Optional[dict] = None) -> np.ndarray:
        return self.sd.output(placeholders or {}, [self.name])[self.name]

    def get_arr(self) -> Optional[np.ndarray]:
        a = self.sd._arrays.get(self.name)
        return np.asarray(a) if a is not None else None

    def set_arr(self, value):
        self.sd._arrays[self.name] = jnp.asarray(value)
        # constant values are baked into cached executors; invalidate
        if self.var_type is VariableType.CONSTANT:
            self.sd._exec_cache.clear()

    def __repr__(self):
        return (f"SDVariable(name='{self.name}', "
                f"type={self.var_type.value}, shape={self.shape})")


class OpNode:
    __slots__ = ("op_name", "inputs", "outputs", "attrs")

    def __init__(self, op_name, inputs, outputs, attrs):
        self.op_name = op_name
        self.inputs = inputs       # list of variable names
        self.outputs = outputs     # list of variable names
        self.attrs = attrs or {}


def _shard_placeholders(mesh, ph_vals: Dict, batch_names=None,
                        specs=None):
    """Shared DP placeholder contract of ``output(mesh=)`` and
    ``fit_steps(mesh=)``: batch dims shard over the mesh's ``data``
    axis, scalars replicate (``shard_batch`` passes them through),
    indivisible batches are rejected loudly. ``specs`` maps
    placeholder names to explicit ``PartitionSpec``s (or axis-name
    tuples) — those placeholders skip inference entirely and are
    device_put at the requested sharding, the escape hatch when the
    batch-dim vote below would guess wrong. Returns
    ``(ph_vals, mesh_sig)``; ``mesh_sig`` keys compiled-program
    caches (None when no mesh) and folds the explicit specs in."""
    if mesh is None:
        return ph_vals, None
    from jax.sharding import NamedSharding, PartitionSpec
    from deeplearning4j_tpu.parallel import replicate_tree, shard_batch
    if "data" not in mesh.axis_names:
        raise ValueError(
            f"mesh must have a 'data' axis, got {mesh.axis_names}")
    ndev = mesh.shape["data"]
    specs = dict(specs or {})
    for k in specs:
        if k not in ph_vals:
            raise ValueError(
                f"placeholder spec for unknown placeholder {k!r} "
                f"(have {sorted(ph_vals)})")
        if not isinstance(specs[k], PartitionSpec):
            sp = specs[k]
            specs[k] = PartitionSpec(*sp) if isinstance(
                sp, (tuple, list)) else PartitionSpec(sp)
    # batch placeholders shard; everything else replicates (GSPMD
    # semantics are identical either way; only batch tensors gain from
    # sharding). "Batch" = the leading dim of the feature/label-mapped
    # placeholders when the caller knows them (fit_steps passes the
    # TrainingConfig mappings); otherwise inferred as the most common
    # leading dim among non-scalar placeholders — ties break toward
    # dims that divide the data axis, then higher rank ([B,T] batch
    # outranks a [T] aux), then size
    batch = None
    inferred = False
    if batch_names:
        for k in batch_names:
            v = ph_vals.get(k)
            if v is not None and v.ndim > 0:
                batch = int(v.shape[0])
                break
    if batch is None:
        leads: dict = {}
        ranks: dict = {}
        for k, v in ph_vals.items():
            if k not in specs and v.ndim > 0:
                d = int(v.shape[0])
                leads[d] = leads.get(d, 0) + 1
                ranks[d] = max(ranks.get(d, 0), v.ndim)
        if leads:
            inferred = True
            batch = max(leads, key=lambda d: (
                leads[d], d % ndev == 0, ranks[d], d))
    if inferred:
        # the vote can be outvoted by aux placeholders that merely
        # share a leading dim: every loser gets REPLICATED, silently
        # giving up DP batch sharding for it (and bypassing the
        # divisibility check it would have hit as a batch tensor) —
        # warn about ANY excluded candidate, not just exact ties
        excluded = sorted(
            k for k, v in ph_vals.items()
            if k not in specs and v.ndim > 0
            and int(v.shape[0]) != batch)
        if excluded:
            log.warning(
                "batch-dim inference chose leading dim %d — "
                "placeholders %s (other leading dims) will be "
                "replicated, not batch-sharded. Pass explicit "
                "data_set_feature_mapping/label_mapping (or "
                "batch_names), or per-placeholder specs "
                "(ph_specs=...), to disambiguate.",
                batch, excluded)
    out = {}
    for k, v in ph_vals.items():
        if k in specs:
            out[k] = jax.device_put(v, NamedSharding(mesh, specs[k]))
        elif v.ndim > 0 and int(v.shape[0]) == batch:
            if v.shape[0] % ndev:
                raise ValueError(
                    f"placeholder {k!r} batch dim {v.shape} not "
                    f"divisible by data axis size {ndev}")
            out[k] = shard_batch(mesh, v)
        else:
            out[k] = replicate_tree(mesh, v)
    return out, (
        tuple(mesh.axis_names),
        tuple(int(mesh.shape[a]) for a in mesh.axis_names),
        tuple(sorted((k, tuple(sp)) for k, sp in specs.items())))


def _write_samediff_zip(path, graph: dict, arrays: dict,
                        cf_arrays: dict, upd_leaves):
    """Write the SameDiff zip from already-host-resident state (shared
    by ``save`` and the async checkpoint snapshot's background
    ``write``)."""
    with zipfile.ZipFile(path, "w") as z:
        z.writestr("graph.json", json.dumps(graph, indent=1))
        buf = io.BytesIO()
        np.savez(buf, **arrays, **cf_arrays)
        z.writestr("arrays.npz", buf.getvalue())
        if upd_leaves is not None:
            buf2 = io.BytesIO()
            np.savez(buf2, **{f"leaf_{i}": l
                              for i, l in enumerate(upd_leaves)})
            z.writestr("updater.npz", buf2.getvalue())


class SameDiff:
    """The graph. Build with var/constant/placeholder + op namespaces
    (sd.math, sd.nn, sd.cnn, sd.rnn, sd.loss, sd.image, sd.bitwise,
    sd.linalg, sd.random); run with output()/fit()."""

    def __init__(self):
        self.vars: Dict[str, SDVariable] = {}
        self.ops: List[OpNode] = []
        self._arrays: Dict[str, jnp.ndarray] = {}   # VARIABLE/CONSTANT
        self._producer: Dict[str, int] = {}          # var name -> op idx
        self._name_counter: Dict[str, int] = {}
        self._exec_cache: Dict = {}
        self._rng = jax.random.PRNGKey(0)
        self.loss_variables: List[str] = []
        self.training_config = None
        self._updater_state = None
        #: DpFlatSpec of the fsdp fit_steps window (parallel.zero);
        #: set by _build_raw_train_step under the fsdp exchange
        self._fsdp_spec = None
        #: updater iteration, persisted across fit()/fit_steps() calls
        #: (Adam bias correction must not restart per call)
        self.iteration_count: int = 0
        self.epoch_count: int = 0
        #: TrainingListener bus (reference: SameDiff.setListeners /
        #: ListenerList — the SAME listener impls MLN/graph use:
        #: Score/Performance/Evaluative/Checkpoint attach unchanged)
        self.listeners: list = []
        self._retrace_guard = None
        self._score: float = float("nan")
        self.last_batch_size: int = 0
        #: sqrt(N) activation checkpointing for TRAINING programs:
        #: the op walk is cut into this many jax.checkpoint segments
        #: (only segment-boundary values are stored for backward).
        #: The memory lever for FLAT imported graphs, which have no
        #: layer structure to remat (see set_remat_segments)
        self.remat_segments: int = 0
        #: foreign-var captures (control-flow bodies closing over a
        #: parent graph): local name -> (owner SameDiff, owner name)
        self._captures: Dict[str, tuple] = {}
        #: names of this graph's VARIABLEs frozen into the closures of
        #: subgraphs owned by UNRELATED graphs — baked per compile, so
        #: fit() drops compiled programs after updating one of them.
        #: (Captures within one tracing chain — direct or nested — are
        #: live op inputs and never land here.)
        self._frozen_captured_vars: set = set()
        #: set while this graph is being traced as a control-flow
        #: subgraph (enables foreign-var capture in _op)
        self._tracing_parent = None
        from deeplearning4j_tpu.autodiff.opsets import (SDBitwise, SDCNN,
                                                        SDImage, SDLinalg,
                                                        SDLoss, SDMath,
                                                        SDNN, SDRandom,
                                                        SDRNN)
        self.math = SDMath(self)
        self.nn = SDNN(self)
        self.cnn = SDCNN(self)
        self.rnn = SDRNN(self)
        self.loss = SDLoss(self)
        self.image = SDImage(self)
        self.bitwise = SDBitwise(self)
        self.linalg = SDLinalg(self)
        self.random = SDRandom(self)

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    # -- naming --------------------------------------------------------
    def _unique(self, base: str) -> str:
        if base not in self.vars and base not in self._name_counter:
            self._name_counter[base] = 0
            return base
        n = self._name_counter.get(base, 0)
        while True:                      # skip user-taken suffixed names
            n += 1
            cand = f"{base}_{n}"
            if cand not in self.vars:
                self._name_counter[base] = n
                return cand

    def _rename(self, old: str, new: str):
        if new in self.vars:
            raise ValueError(f"variable '{new}' already exists")
        v = self.vars.pop(old)
        v.name = new
        self.vars[new] = v
        if old in self._arrays:
            self._arrays[new] = self._arrays.pop(old)
        if old in self._producer:
            self._producer[new] = self._producer.pop(old)
        for op_node in self.ops:
            op_node.inputs = [new if i == old else i
                              for i in op_node.inputs]
            op_node.outputs = [new if o == old else o
                               for o in op_node.outputs]
        self.loss_variables = [new if n == old else n
                               for n in self.loss_variables]
        self._exec_cache.clear()

    # -- variable creation (reference: sd.var/constant/placeHolder) ----
    def var(self, name: Optional[str] = None, shape=None,
            dtype=jnp.float32, *, init=None, array=None) -> SDVariable:
        """Trainable variable. Provide ``array`` or (``shape`` +
        optional weight-init ``init`` (WeightInit or callable))."""
        name = self._unique(name or "var")
        if array is not None:
            arr = jnp.asarray(array)
        else:
            if shape is None:
                raise ValueError("var needs shape or array")
            self._rng, k = jax.random.split(self._rng)
            if init is None:
                arr = jnp.zeros(shape, dtype)
            elif callable(getattr(init, "init", None)):
                fan_in = shape[0] if len(shape) >= 1 else 1
                fan_out = shape[-1] if len(shape) >= 2 else 1
                arr = init.init(k, tuple(shape), fan_in, fan_out, dtype)
            else:
                arr = init(k, tuple(shape), dtype)
        v = SDVariable(self, name, VariableType.VARIABLE, arr.shape,
                       arr.dtype)
        self.vars[name] = v
        self._arrays[name] = arr
        return v

    def constant(self, name_or_array, array=None) -> SDVariable:
        if array is None:
            name, array = None, name_or_array
        else:
            name = name_or_array
        arr = jnp.asarray(array)
        name = self._unique(name or "const")
        v = SDVariable(self, name, VariableType.CONSTANT, arr.shape,
                       arr.dtype)
        self.vars[name] = v
        self._arrays[name] = arr
        return v

    def placeholder(self, name: str, shape=None,
                    dtype=jnp.float32) -> SDVariable:
        name = self._unique(name)
        v = SDVariable(self, name, VariableType.PLACEHOLDER, shape, dtype)
        self.vars[name] = v
        return v

    place_holder = placeholder     # reference spelling

    def convert_to_variables(self, names: Sequence,
                             values: Optional[dict] = None):
        """Promote placeholders/constants to trainable VARIABLEs
        (reference: SameDiff.convertToVariable(s) — used after import
        to make trained tensors differentiable/trainable). ``values``
        supplies initial arrays for converted placeholders."""
        for n in names:
            name = n.name if isinstance(n, SDVariable) else n
            v = self.vars[name]
            if values and name in values:
                arr = jnp.asarray(values[name])
                self._arrays[name] = arr
                v.shape, v.dtype = arr.shape, arr.dtype
            if name not in self._arrays:
                raise ValueError(
                    f"convert_to_variables('{name}'): no stored value "
                    f"— pass one via values={{'{name}': array}}")
            v.var_type = VariableType.VARIABLE
        self._exec_cache.clear()

    convertToVariables = convert_to_variables

    def _as_var(self, x) -> SDVariable:
        if isinstance(x, SDVariable):
            return x
        return self.constant(jnp.asarray(x))

    # -- op creation ---------------------------------------------------
    def _op(self, op_name: str, inputs: Sequence[SDVariable],
            attrs: Optional[dict] = None, name: Optional[str] = None,
            n_out: int = 1) -> Union[SDVariable, Tuple[SDVariable, ...]]:
        get_op(op_name)               # validate early
        for v in inputs:
            if isinstance(v, SDVariable) and v.sd is not self and \
                    self._tracing_parent is None:
                raise ValueError(
                    f"variable '{v.name}' belongs to another SameDiff "
                    f"graph (cross-graph references are only valid "
                    f"inside control-flow bodies)")
        inputs = [self._import_foreign(v) if isinstance(v, SDVariable)
                  and v.sd is not self else v for v in inputs]
        in_names = [v.name for v in inputs]
        if n_out == 1:
            out_names = [self._unique(name or op_name)]
        else:
            base = name or op_name
            out_names = [self._unique(f"{base}:{i}")
                         for i in range(n_out)]
        node = OpNode(op_name, in_names, out_names, attrs)
        idx = len(self.ops)
        self.ops.append(node)
        outs = []
        for on in out_names:
            v = SDVariable(self, on, VariableType.ARRAY)
            self.vars[on] = v
            self._producer[on] = idx
            outs.append(v)
        self._exec_cache.clear()
        return outs[0] if n_out == 1 else tuple(outs)

    def invoke(self, op_name, inputs, attrs=None, name=None, n_out=1):
        """Public escape hatch: call any registered op by name."""
        return self._op(op_name, [self._as_var(i) for i in inputs],
                        attrs, name, n_out)

    def _import_foreign(self, v: "SDVariable") -> "SDVariable":
        """A var of ANOTHER SameDiff used here (control-flow bodies
        closing over parent vars): register it under a local capture
        name so it can never collide with this graph's own names —
        the subgraph runner resolves captures from the owner at call
        time."""
        for local, (sd, pname) in self._captures.items():
            if sd is v.sd and pname == v.name:
                return self.vars[local]
        local = self._unique(f"_cap_{v.name}")
        proxy = SDVariable(self, local, VariableType.PLACEHOLDER,
                           v.shape, v.dtype)
        self.vars[local] = proxy
        self._captures[local] = (v.sd, v.name)
        return proxy


    # -- execution -----------------------------------------------------
    def _ancestors(self, targets: Sequence[str]) -> List[int]:
        """Op indices needed to compute ``targets``, in execution order."""
        needed: set = set()
        stack = list(targets)
        seen_vars = set()
        while stack:
            vn = stack.pop()
            if vn in seen_vars:
                continue
            seen_vars.add(vn)
            if vn in self._producer:
                idx = self._producer[vn]
                if idx not in needed:
                    needed.add(idx)
                    stack.extend(self.ops[idx].inputs)
        return sorted(needed)

    def _execute(self, values: dict, op_indices: List[int], rng,
                 training: bool):
        for idx in op_indices:
            node = self.ops[idx]
            attrs = node.attrs
            if node.op_name in RNG_OPS:
                attrs = dict(attrs)
                attrs["rng"] = (jax.random.fold_in(rng, idx)
                                if rng is not None else None)
                if node.op_name == "dropout":
                    attrs["training"] = training
            ins = [values[i] for i in node.inputs]
            # layer-attribution scope (common.layerprof): tag the op's
            # trace — fwd and its autodiff transpose — with the first
            # output's name, so imported-graph HLO carries op identity
            with layerprof.scope("sd." + node.outputs[0]):
                out = get_op(node.op_name)(ins, attrs)
            if len(node.outputs) == 1:
                values[node.outputs[0]] = out
            else:
                for on, o in zip(node.outputs, out):
                    values[on] = o
        return values

    def _required_placeholders(self, op_indices, out_names):
        needed = set(out_names)
        for idx in op_indices:
            needed.update(self.ops[idx].inputs)
        return {n for n in needed
                if n in self.vars and
                self.vars[n].var_type is VariableType.PLACEHOLDER}

    def _build_fn(self, out_names: Tuple[str, ...], ph_names: Tuple[str,
                  ...], training: bool):
        op_indices = self._ancestors(list(out_names))
        missing = self._required_placeholders(op_indices, out_names) \
            - set(ph_names)
        if missing:
            raise ValueError(
                f"missing placeholder values for {sorted(missing)} "
                f"(required to compute {list(out_names)}; "
                f"provided: {sorted(ph_names)})")
        # restrict to the requested subgraph: variables/constants outside
        # it must not be shipped per call nor receive l1/l2 gradients
        needed = set(out_names)
        for idx in op_indices:
            needed.update(self.ops[idx].inputs)
        const_vals = {n: a for n, a in self._arrays.items()
                      if n in needed and
                      self.vars[n].var_type is VariableType.CONSTANT}
        var_names = [n for n, v in self.vars.items()
                     if n in needed and
                     v.var_type is VariableType.VARIABLE]

        def fn(var_vals: dict, ph_vals: dict, rng):
            values = dict(const_vals)
            values.update(var_vals)
            values.update(ph_vals)
            if training and self.remat_segments > 1 \
                    and len(op_indices) > 1:
                self._execute_segmented(values, op_indices, rng,
                                        training, out_names)
            else:
                self._execute(values, op_indices, rng, training)
            return [values[n] for n in out_names]

        return fn, var_names

    def _segment_cut_costs(self, op_indices: List[int],
                           out_names: Tuple[str, ...],
                           sizes: Optional[dict] = None):
        """``cost[c]`` = BYTES of intermediate values live across a
        cut placed before walk position ``c`` (produced earlier,
        consumed at/after ``c`` or a requested output) — the storage
        ``min_cut_segment_plan`` minimizes. ``sizes`` maps value name
        -> bytes (from the abstract shape pass); a missing entry
        counts 1, so with no size info this degrades to live-value
        counting."""
        n = len(op_indices)
        first_prod = {}
        last_read = {}
        for j, i in enumerate(op_indices):
            for name in self.ops[i].inputs:
                last_read[name] = j
            for name in self.ops[i].outputs:
                first_prod.setdefault(name, j)
        sizes = sizes or {}
        diff = np.zeros(n + 2)
        for name, j in first_prod.items():
            k = n if name in out_names else last_read.get(name, j)
            if k > j:
                w = float(sizes.get(name, 1.0))
                # crosses every cut c with j < c <= k
                diff[j + 1] += w
                diff[k + 1] -= w
        return np.cumsum(diff)[:n + 1]

    def _value_sizes(self, values: dict, op_indices: List[int], rng,
                     training: bool) -> dict:
        """Byte size of every intermediate value, via ONE abstract
        (shape-only) pass over the walk — jax.eval_shape runs no
        FLOPs and allocates nothing. Empty on failure (the cut costs
        then fall back to live-value counts)."""
        in_structs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                      for k, v in values.items()
                      if hasattr(v, "shape") and hasattr(v, "dtype")}

        def walk(vals_in):
            vals = dict(values)
            vals.update(vals_in)
            self._execute(vals, op_indices, rng, training)
            return vals

        try:
            out = jax.eval_shape(walk, in_structs)
            return {k: int(np.prod(v.shape)) * v.dtype.itemsize
                    for k, v in out.items()
                    if hasattr(v, "shape") and v.shape is not None}
        except Exception as e:                    # noqa: BLE001
            log.debug("abstract size pass failed (%s); min-cut falls "
                      "back to live-value counts", e)
            return {}

    def fuse_attention_patterns(self) -> int:
        """Attention-fusion pass (reference role: SameDiff's
        GraphOptimizer/OptimizationConfig): recognize the exporter's
        op-by-op attention and rewrite each occurrence to ONE fused
        ``sdpa_core`` op — now one pass of the full pipeline in
        autodiff.passes (see :meth:`optimize`). Kept as a standalone
        entry point for API compatibility: returns the number of
        sites fused; compiled-program caches are dropped when > 0."""
        from deeplearning4j_tpu.autodiff.passes import attention_fuse
        fused = attention_fuse(self)
        if fused:
            self._exec_cache.clear()
        return fused

    def optimize(self, passes=None) -> Dict[str, int]:
        """Run the full GraphOptimizer pass pipeline (autodiff.passes):
        cast folding, mask strength reduction, LayerNorm/GELU
        re-fusion, attention fusion — ordered, iterated to fixpoint.
        Importers invoke this automatically post-import unless
        DL4J_TPU_GRAPHOPT=0. Returns per-pass rewrite counts."""
        from deeplearning4j_tpu.autodiff.passes import GraphOptimizer
        return GraphOptimizer(self, passes=passes).run()

    def set_remat_segments(self, n: int):
        """Cut TRAINING forward programs into ``n`` ``jax.checkpoint``
        segments of the op walk (sqrt(N) activation checkpointing):
        only segment-boundary values are stored for backward,
        interiors are recomputed. This is the memory lever for flat
        IMPORTED graphs, which have no layer boundaries to remat —
        e.g. imported BERT-base OOMs at batch 1024 without it
        (BENCH_notes_r04.md). 0 disables. Compiled training programs
        bake the setting, so the caches are dropped."""
        self.remat_segments = int(n)
        self._exec_cache.clear()
        return self

    def _execute_segmented(self, values: dict, op_indices: List[int],
                           rng, training: bool,
                           out_names: Tuple[str, ...]):
        """The op walk in ``remat_segments`` contiguous
        ``jax.checkpoint`` segments, with liveness analysis so only
        values consumed later (or requested outputs) cross segment
        boundaries. Boundaries are MIN-CUT placed (fewest live values
        stored — on a flat imported transformer that finds the layer
        boundaries, where only the hidden state crosses, instead of
        cutting mid-attention where the O(t^2) scores are live). The
        per-op RNG is ``fold_in(rng, op idx)`` (same as the plain
        walk), so segmentation does not change the stream."""
        from deeplearning4j_tpu.common.remat import min_cut_segment_plan
        read_at = [set(self.ops[i].inputs) for i in op_indices]
        sizes = self._value_sizes(values, op_indices, rng, training)
        plan = min_cut_segment_plan(
            len(op_indices), self.remat_segments,
            self._segment_cut_costs(op_indices, out_names, sizes))
        for lo, hi, wrap in plan:
            seg = op_indices[lo:hi]
            produced = set()
            for i in seg:
                produced.update(self.ops[i].outputs)
            read = set()
            for j in range(lo, hi):
                read.update(read_at[j])
            needed_after = set(out_names)
            for j in range(hi, len(op_indices)):
                needed_after.update(read_at[j])
            seg_in = sorted((read - produced) & set(values))
            seg_out = sorted(produced & needed_after)

            def seg_fn(in_vals, seg=seg, seg_out=seg_out):
                vals = dict(in_vals)
                self._execute(vals, seg, rng, training)
                return {k: vals[k] for k in seg_out}

            if wrap:
                seg_fn = jax.checkpoint(seg_fn)
            outs = seg_fn({k: values[k] for k in seg_in})
            # prune: drop values dead past this boundary, keep the
            # rest (constants/vars/placeholders live in `values` too
            # and are needed by later segments' seg_in gathers)
            for k in list(values):
                if k not in needed_after:
                    del values[k]
            values.update(outs)

    def output(self, placeholders: dict, outputs: Sequence[str],
               *, training: bool = False,
               mesh=None, ph_specs=None) -> Dict[str, np.ndarray]:
        """Execute the graph (reference: SameDiff.output). The whole
        requested subgraph compiles to one XLA program, cached per
        (outputs, placeholder signature).

        ``mesh``: a ``jax.sharding.Mesh`` with a ``data`` axis runs
        inference DATA-PARALLEL — placeholder batch dims shard over
        ``data``, variables replicate (the batched-inference half of
        ``fit_steps(mesh=...)``). ``ph_specs`` maps placeholder names
        to explicit ``PartitionSpec``s when the batch-dim inference
        would guess wrong (see ``_shard_placeholders``)."""
        outputs = [o.name if isinstance(o, SDVariable) else o
                   for o in outputs]
        ph_vals = {k: jnp.asarray(v) for k, v in placeholders.items()}
        cfg = self.training_config
        ph_vals, mesh_sig = _shard_placeholders(
            mesh, ph_vals,
            batch_names=(cfg.data_set_feature_mapping +
                         cfg.data_set_label_mapping) if cfg else None,
            specs=ph_specs)
        sig = (tuple(outputs), training, mesh_sig,
               tuple(sorted((k, v.shape, str(v.dtype))
                            for k, v in ph_vals.items())))
        if sig not in self._exec_cache:
            _, _, fn, var_vals = self._prepare(placeholders, outputs,
                                               training)
            self._exec_cache[sig] = (jax.jit(fn), list(var_vals))
        jfn, var_names = self._exec_cache[sig]
        var_vals = {n: self._arrays[n] for n in var_names}
        if mesh is not None:
            from deeplearning4j_tpu.parallel import replicate_tree
            var_vals = replicate_tree(mesh, var_vals)
        self._rng, rng = jax.random.split(self._rng)
        res = jfn(var_vals, ph_vals, rng)
        return {n: np.asarray(r) for n, r in zip(outputs, res)}

    def _prepare(self, placeholders: dict, outputs: Sequence[str],
                 training: bool):
        """Shared preamble of output/to_stablehlo/export_serialized:
        name normalization, placeholder coercion, subgraph build,
        variable-value gather."""
        outputs = tuple(o.name if isinstance(o, SDVariable) else o
                        for o in outputs)
        ph_vals = {k: (v if isinstance(v, jax.ShapeDtypeStruct)
                       else jnp.asarray(v))
                   for k, v in placeholders.items()}
        fn, var_names = self._build_fn(outputs, tuple(ph_vals),
                                       training)
        var_vals = {n: self._arrays[n] for n in var_names}
        return outputs, ph_vals, fn, var_vals

    def to_stablehlo(self, placeholders: dict,
                     outputs: Sequence[str],
                     *, training: bool = False) -> str:
        """StableHLO text of the ONE compiled program this subgraph
        lowers to (SURVEY.md §2.7 item 1: the "StableHLO graph
        emitter" role of the reference's native graph runtime —
        here the emitter is the jax lowering of the already-built
        program; this is the portable, inspectable artifact).
        ``placeholders`` supply shapes/dtypes (arrays or
        ShapeDtypeStruct)."""
        _, ph_vals, fn, var_vals = self._prepare(placeholders,
                                                 outputs, training)
        lowered = jax.jit(fn).lower(var_vals, ph_vals,
                                    jax.random.PRNGKey(0))
        return lowered.as_text()

    def export_serialized(self, placeholders: dict,
                          outputs: Sequence[str],
                          *, training: bool = False) -> bytes:
        """Portable serialized program (``jax.export`` bytes: versioned
        StableHLO + calling convention) — the AOT hand-off artifact
        for serving runtimes.  The RNG key stays a program INPUT so
        stochastic graphs (dropout, random ops) are reseedable per
        call.  Round-trips with :func:`deserialize_and_call`."""
        from jax import export as jax_export
        _, ph_vals, fn, var_vals = self._prepare(placeholders,
                                                 outputs, training)

        def closed(ph, rng):
            return fn(var_vals, ph, rng)

        args = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in ph_vals.items()}
        key_spec = jax.ShapeDtypeStruct(
            jax.random.PRNGKey(0).shape,
            jax.random.PRNGKey(0).dtype)
        exported = jax_export.export(jax.jit(closed))(args, key_spec)
        return bytes(exported.serialize())

    @staticmethod
    def deserialize_and_call(blob: bytes, placeholders: dict,
                             seed: int = 0):
        """Run a program serialized by :meth:`export_serialized`."""
        from jax import export as jax_export
        exported = jax_export.deserialize(bytearray(blob))
        return exported.call({k: jnp.asarray(v)
                              for k, v in placeholders.items()},
                             jax.random.PRNGKey(seed))

    # -- control flow (SURVEY.md S3 / Appendix A) ----------------------
    def _trace_subgraph(self, fn, n_args: int):
        """Trace a python-function subgraph into a CHILD SameDiff and
        return (callable, n_outputs). The callable replays the child
        graph on traced values — so while/cond/scan bodies lower into
        the parent's XLA program as lax control flow.

        Child-graph VARIABLES are frozen into the closure as constants
        (loop bodies can't own trainable state; thread it through the
        carry instead)."""
        child = SameDiff()
        child._tracing_parent = self
        proxies = [child.placeholder(f"_arg{i}", shape=None)
                   for i in range(n_args)]
        try:
            # zero-arg bodies have no proxy to learn the child graph
            # from; publish it on the callable (the TF importer's
            # function bodies read this to emit into the right graph)
            fn._trace_child_sd = child
        except (AttributeError, TypeError):
            pass
        res = fn(*proxies) if n_args else fn()
        outs = list(res) if isinstance(res, (list, tuple)) else [res]
        outs = [(o if o.sd is child else child._import_foreign(o))
                if isinstance(o, SDVariable) else child._as_var(o)
                for o in outs]
        out_names = [o.name for o in outs]
        proxy_names = [p.name for p in proxies]
        idxs = child._ancestors(out_names)
        # Closure capture: foreign vars the body referenced were
        # registered under collision-proof local names
        # (_import_foreign) mapping back to their owner graph.
        # Captures owned by THIS graph become extra op INPUTS — live,
        # differentiable values at runtime (a captured trainable
        # receives gradients through cond/scan and through
        # while_loop(max_iterations=N); an UNBOUNDED while_loop
        # raises on any gradient request through its outputs — XLA
        # while has no reverse rule, and silence would train wrong).
        # Captures owned by a graph FURTHER UP the tracing chain
        # (nested subgraphs) re-capture level-by-level, so they stay
        # live op inputs at every level and gradients flow the same
        # way.  Only captures of a genuinely UNRELATED graph are
        # frozen at trace time; their owner drops compiled programs
        # when such a variable trains.
        parent_caps = []     # (local_name, parent_name)
        frozen_caps = []     # (local_name, owner, owner_name)
        for local, (owner, pname) in child._captures.items():
            if owner is self:
                parent_caps.append((local, pname))
                continue
            anc = self._tracing_parent
            while anc is not None and anc is not owner:
                anc = anc._tracing_parent
            if anc is owner:
                # thread LIVE through this intermediate graph: the
                # re-captured proxy becomes a real op input here and
                # resolves one level up on the next trace
                proxy = self._import_foreign(owner.vars[pname])
                parent_caps.append((local, proxy.name))
                continue
            if pname not in owner._arrays:
                raise ValueError(
                    f"control-flow body captured '{pname}' from an "
                    f"outer subgraph where it has no stored value — "
                    f"thread it through the loop/branch arguments")
            if owner.vars[pname].var_type is VariableType.VARIABLE:
                owner._frozen_captured_vars.add(pname)
            frozen_caps.append((local, owner, pname))

        def call(*args):
            values = dict(child._arrays)
            for local, owner, pname in frozen_caps:
                values[local] = owner._arrays[pname]
            values.update(zip(proxy_names, args[:n_args]))
            values.update({local: v for (local, _), v in
                           zip(parent_caps, args[n_args:])})
            child._execute(values, idxs, None, False)
            return [values[n] for n in out_names]

        cap_vars = [self.vars[pname] for _, pname in parent_caps]
        # serializable description of the subgraph (sd.save writes it;
        # load rebuilds the call closure from it) — the live refs
        # (child, frozen owners) are resolved to arrays at save time
        spec = {"child": child, "frozen_caps": frozen_caps,
                "proxies": proxy_names, "outs": out_names,
                "parent_cap_locals": [l for l, _ in parent_caps]}
        return call, len(out_names), cap_vars, spec

    def while_loop(self, loop_vars: Sequence, cond_fn, body_fn,
                   name: Optional[str] = None,
                   max_iterations: Optional[int] = None):
        """Dynamic loop over the graph (reference: SameDiff whileLoop /
        TF-import Enter..Exit frames). ``cond_fn`` maps the loop vars
        to a scalar boolean; ``body_fn`` returns updated loop vars
        (same count/shapes).

        With ``max_iterations=N`` (TF ``maximum_iterations``
        semantics) the loop lowers to a bounded masked ``lax.scan`` —
        fully reverse-differentiable through loop vars and captures,
        truncating after N trips. Without it, the loop lowers to
        ``lax.while_loop``: unbounded, but forward-only — a gradient
        request through it raises loudly (never silently zeros)."""
        loop_vars = [self._as_var(v) for v in loop_vars]
        n = len(loop_vars)
        cond_call, _, cond_caps, cond_spec = self._trace_subgraph(
            cond_fn, n)
        body_call, n_body, body_caps, body_spec = self._trace_subgraph(
            body_fn, n)
        if n_body != n:
            raise ValueError(f"while_loop body returned {n_body} vars "
                             f"for {n} loop vars")
        return self._op("while_loop",
                        loop_vars + cond_caps + body_caps,
                        {"_cond_call": cond_call,
                         "_body_call": body_call,
                         "_cond_spec": cond_spec,
                         "_body_spec": body_spec,
                         "n_loop": n,
                         "n_cond_caps": len(cond_caps),
                         "n_body_caps": len(body_caps),
                         "max_iterations": max_iterations},
                        name=name, n_out=n)

    def cond(self, pred, true_fn, false_fn, operands: Sequence = (),
             name: Optional[str] = None):
        """``lax.cond`` (reference: TF-import Switch/Merge pairs).
        Both branches take ``operands`` and must return the same
        number of outputs. Differentiable."""
        operands = [self._as_var(v) for v in operands]
        t_call, nt, t_caps, t_spec = self._trace_subgraph(true_fn,
                                                          len(operands))
        f_call, nf, f_caps, f_spec = self._trace_subgraph(false_fn,
                                                          len(operands))
        if nt != nf:
            raise ValueError(f"cond branches disagree: {nt} vs {nf} "
                             f"outputs")
        return self._op("cond",
                        [self._as_var(pred)] + operands
                        + t_caps + f_caps,
                        {"_true_call": t_call, "_false_call": f_call,
                         "_true_spec": t_spec, "_false_spec": f_spec,
                         "n_operands": len(operands),
                         "n_true_caps": len(t_caps),
                         "n_false_caps": len(f_caps)},
                        name=name, n_out=nt)

    def scan(self, body_fn, init: Sequence, xs: Sequence = (),
             length: Optional[int] = None,
             name: Optional[str] = None):
        """``lax.scan``: ``body_fn(*carry, *x_slices) -> (new_carry...,
        y_outputs...)``. Returns final carries followed by stacked
        per-step outputs. Differentiable — the trainable-loop form
        (reference tBPTT-style loops compile to this)."""
        init = [self._as_var(v) for v in init]
        xs = [self._as_var(v) for v in xs]
        body_call, n_total, caps, body_spec = self._trace_subgraph(
            body_fn, len(init) + len(xs))
        if n_total < len(init):
            raise ValueError("scan body must return at least the "
                             "carry")
        return self._op("scan", init + xs + caps,
                        {"_body_call": body_call,
                         "_body_spec": body_spec,
                         "n_carry": len(init), "n_xs": len(xs),
                         "length": length},
                        name=name, n_out=n_total)

    def batch_output(self):
        """Fluent executor (reference: sd.batchOutput())."""
        sd = self

        class _Builder:
            def __init__(self):
                self._ph = {}
                self._outs = []

            def input(self, name, arr):
                self._ph[name if isinstance(name, str) else name.name] \
                    = arr
                return self

            def output(self, *names):
                self._outs.extend(n if isinstance(n, str) else n.name
                                  for n in names)
                return self

            def output_all(self):
                self._outs = [n for n, v in sd.vars.items()
                              if v.var_type is VariableType.ARRAY]
                return self

            def exec(self):
                return sd.output(self._ph, self._outs)

        return _Builder()

    # -- gradients (S2) ------------------------------------------------
    def set_loss_variables(self, *names):
        # accept varargs or a single list/tuple (reference overloads)
        if len(names) == 1 and isinstance(names[0], (list, tuple)):
            names = names[0]
        self.loss_variables = [n.name if isinstance(n, SDVariable) else n
                               for n in names]

    def calculate_gradients(self, placeholders: dict,
                            wrt: Sequence[str]) -> Dict[str, np.ndarray]:
        """Analytic gradients of the summed loss variables wrt the given
        VARIABLEs (reference: sd.calculateGradients)."""
        if not self.loss_variables:
            raise ValueError("call set_loss_variables first")
        wrt = [w.name if isinstance(w, SDVariable) else w for w in wrt]
        ph_vals = {k: jnp.asarray(v) for k, v in placeholders.items()}
        fn, var_names = self._build_fn(tuple(self.loss_variables),
                                       tuple(ph_vals), True)

        def loss_fn(wrt_vals):
            var_vals = {n: self._arrays[n] for n in var_names
                        if n not in wrt_vals}
            var_vals.update(wrt_vals)
            # deterministic key so random ops in the loss subgraph work
            outs = fn(var_vals, ph_vals, jax.random.PRNGKey(0))
            return sum(jnp.sum(o) for o in outs)

        grads = jax.grad(loss_fn)({n: self._arrays[n] for n in wrt})
        return {n: np.asarray(g) for n, g in grads.items()}

    # -- training (S4) -------------------------------------------------
    def set_training_config(self, config):
        self.training_config = config
        # compiled train steps bake the updater/regularization in
        self._exec_cache = {
            k: v for k, v in self._exec_cache.items()
            if not (isinstance(k, tuple) and k
                    and k[0] in ("train", "train_multi"))}

    def _build_raw_train_step(self, ph_names: Tuple[str, ...],
                              mesh=None, axis: str = "data",
                              mode="dense", tp_specs=None,
                              encoding=None):
        """The unjitted train step over one flat tree of variables:
        loss and gradients here, the update tail for the resolved
        ``UpdateExchange`` ``mode`` in ``parallel.zero.apply_update``
        (the whole variable tree is its one entry)."""
        from deeplearning4j_tpu.parallel import zero
        mode = zero.UpdateExchange(mode)
        cfg = self.training_config
        fn, var_names = self._build_fn(tuple(self.loss_variables),
                                       ph_names, True)
        trainable = [n for n in var_names]
        updater = cfg.updater
        tp_specs = ({n: s for n, s in (tp_specs or {}).items()
                     if n in trainable} if mesh is not None else {})

        def dense_loss(tv, ph_vals, rng):
            if tp_specs:
                # 2D mode: pin tp variables to their compute spec; the
                # custom-vjp pin sends the cotangent to the resident
                # spec, so dp grad collectives stay on the data axis
                tv = zero.pin_tp_entry(tv, mesh, tp_specs)
            outs = fn(tv, ph_vals, rng)
            total = sum(jnp.sum(o) for o in outs)
            if cfg.l2:
                total = total + 0.5 * cfg.l2 * sum(
                    jnp.sum(v * v) for v in tv.values())
            if cfg.l1:
                total = total + cfg.l1 * sum(
                    jnp.sum(jnp.abs(v)) for v in tv.values())
            return total

        loss_of = dense_loss
        if mode is zero.UpdateExchange.FSDP:
            # ZeRO-3: var_vals travel as the single flat shard dict
            # ({FSDP_KEY: {dtype: flat}}, resident 1/N along the data
            # axis); the forward gathers them through the custom-vjp
            # gather, so the grad cotangent is born reduce-scattered
            # and the tail never all-gathers the new variables.
            # Tensor-parallel variables (tp_specs) never enter the
            # flats: they ride under TP_KEY at full logical shape,
            # resident-sharded over model(×data) via their specs
            from deeplearning4j_tpu.learning.updaters import (
                FSDP_KEY, TP_KEY, dp_flatten_spec)
            spec = dp_flatten_spec(
                {n: self._arrays[n] for n in trainable
                 if n not in tp_specs},
                mesh.shape[axis])
            self._fsdp_spec = spec

            def loss_of(fv, ph_vals, rng):
                tv = zero.fsdp_gather(fv[FSDP_KEY], spec, mesh, axis)
                if tp_specs:
                    # dense_loss pins these to the compute spec
                    tv = {**tv, **fv[TP_KEY]}
                return dense_loss(tv, ph_vals, rng)

        def step(var_vals, upd_state, ph_vals, iteration, rng):
            loss, grads = jax.value_and_grad(
                lambda vv: loss_of(vv, ph_vals, rng))(var_vals)
            # updater math (bias corrections etc.) may run in f32;
            # keep_dtype applies it at full precision, then keeps each
            # variable's own dtype — without the cast, bf16 variables
            # silently promote to f32 after one step (and recompile
            # the step)
            new_vars, new_state = zero.apply_update(
                updater, grads, var_vals, upd_state, iteration,
                mesh=mesh, axis=axis, mode=mode, tp_specs=tp_specs,
                encoding=encoding, keep_dtype=True)
            return new_vars, new_state, loss

        return step, trainable

    def _build_train_step(self, ph_names: Tuple[str, ...]):
        step, trainable = self._build_raw_train_step(ph_names)
        return jax.jit(step, donate_argnums=(0, 1)), trainable

    def fit_steps(self, placeholders: Dict, n_steps: int,
                  mesh=None, update_exchange="auto", tp_specs=None,
                  ph_specs=None, encoding=None) -> float:
        """``n_steps`` train-step updates on ONE fixed placeholder
        batch inside a single ``lax.fori_loop`` dispatch, syncing on
        the final loss once. The benchmark-grade loop (same recipe as
        ``MultiLayerNetwork.fit_steps``): per-step host dispatch + loss
        sync is a fixed tax that the fori-loop amortizes. Per-step RNG is ``fold_in(rng, i)``; the updater
        iteration continues from ``self.iteration_count`` (shared with
        ``fit``), so chained calls don't re-apply Adam bias-correction
        warmup: ``fit_steps(b, 5)`` twice == ``fit_steps(b, 10)``.

        ``mesh``: a ``jax.sharding.Mesh`` with a ``data`` axis trains
        the program DATA-PARALLEL — every placeholder's leading axis
        is sharded over ``data``, variables/updater state are
        replicated, and GSPMD inserts the gradient all-reduce inside
        the compiled step (the ParallelWrapper recipe applied to an
        imported/authored SameDiff program; no reference equivalent —
        SameDiff in the reference is single-device).

        A 2D ``(data, model)`` mesh trains TENSOR-PARALLEL on top:
        eligible variables (``parallel.speclayout`` inference, or an
        explicit ``tp_specs`` name→``TpLeafSpec`` dict) are physically
        sharded over ``model`` and updated through ``apply_update_tp``
        — they never enter the dp flat ravels, so dp collectives stay
        on the ``data`` axis. ``ph_specs`` maps placeholder names to
        explicit ``PartitionSpec``s (see ``_shard_placeholders``).

        ``update_exchange="encoded"`` selects the compressed-collective
        rung: the flat dp gradient is quantized/sparsified before the
        data-axis exchange with per-replica error-feedback residuals
        (``parallel.encoding``); ``encoding=`` takes an
        ``EncodingSpec`` or scheme string (``"threshold"``/``"int8"``/
        ``"1bit"``)."""
        cfg = self.training_config
        if cfg is None:
            raise ValueError("call set_training_config first")
        if not self.loss_variables:
            raise ValueError("call set_loss_variables first")
        ph_vals = {k: jnp.asarray(v) for k, v in placeholders.items()}
        ph_vals, mesh_sig = _shard_placeholders(
            mesh, ph_vals, batch_names=(cfg.data_set_feature_mapping +
                                        cfg.data_set_label_mapping),
            specs=ph_specs)
        from deeplearning4j_tpu.parallel.zero import (
            UpdateExchange, resolve_update_exchange)
        mode = resolve_update_exchange(mesh, requested=update_exchange)
        sharded = mode is UpdateExchange.SHARDED
        fsdp = mode is UpdateExchange.FSDP
        encoded = mode is UpdateExchange.ENCODED
        if encoded:
            from deeplearning4j_tpu.parallel.encoding import \
                resolve_encoding
            encoding = resolve_encoding(encoding)
        else:
            encoding = None
        tp = (int(mesh.shape.get("model", 1)) if mesh is not None
              else 1)
        if mesh is None or tp <= 1:
            tp_specs = {}
        elif tp_specs is None:
            from deeplearning4j_tpu.parallel.speclayout import \
                SpecLayout
            tp_specs = SpecLayout(mesh).infer_entry(
                {n: v for n, v in self._arrays.items()
                 if self.vars[n].var_type is VariableType.VARIABLE},
                shard_over_data=sharded or fsdp or encoded)
        tp_sig = tuple(sorted(
            (n, tuple(s.compute), tuple(s.resident))
            for n, s in tp_specs.items())) or None
        enc_sig = encoding.signature() if encoding is not None else None
        key = (tuple(sorted(ph_vals)), mesh_sig, mode.value, tp_sig,
               enc_sig)
        cached = self._exec_cache.get(("train_multi", key))
        if cached is None:
            raw, trainable = self._build_raw_train_step(
                tuple(ph_vals), mesh, mode=mode, tp_specs=tp_specs,
                encoding=encoding)

            def multi(var_vals, upd_state, ph, rng, it0, n):
                def body(i, carry):
                    vv, us, _ = carry
                    vv, us, loss = raw(vv, us, ph, it0 + i,
                                       jax.random.fold_in(rng, i))
                    return vv, us, jnp.float32(loss)

                return jax.lax.fori_loop(
                    0, n, body,
                    (var_vals, upd_state, jnp.float32(0)))

            cached = (jax.jit(multi, static_argnums=(5,),
                              donate_argnums=(0, 1)), trainable)
            self._exec_cache[("train_multi", key)] = cached
        multi_fn, trainable = cached
        # checked on EVERY call (not just compile): a subgraph traced
        # after the first fit_steps can freeze a trainable into a
        # closure, and the cached fori program would keep reusing the
        # stale baked capture while training the variable
        if self._frozen_captured_vars \
                and self._frozen_captured_vars & set(trainable):
            raise ValueError(
                "fit_steps cannot train variables frozen into "
                "nested-subgraph closures (their values are baked "
                "per compile; the fori-loop would keep reusing "
                "stale captures) — use fit(), which retraces per "
                "step in that case")
        if self._updater_state is None:
            self._updater_state = cfg.updater.init_state(
                {n: self._arrays[n] for n in trainable})
            self._restore_updater_leaves()
        self._updater_trainable = list(trainable)
        var_vals = {n: self._arrays[n] for n in trainable}
        tp_specs = {n: s for n, s in tp_specs.items() if n in var_vals}
        # layout sync: the sharded/fsdp steps consume/produce the
        # ZeRO-1 flat state (tp variables split out under TP_KEY); the
        # dense step the per-variable slot trees
        flat_state = sharded or fsdp or encoded
        from deeplearning4j_tpu.learning.updaters import (has_tp,
                                                          is_dp_sharded,
                                                          is_encoded)
        if encoded and self._updater_state is not None:
            # encoded flats + error-feedback residual injected when
            # absent (first fit, or a dense/sharded checkpoint
            # restored into an encoded run on any device count)
            from deeplearning4j_tpu.parallel.zero import \
                ensure_encoded_state
            self._updater_state = ensure_encoded_state(
                var_vals, self._updater_state, mesh.shape["data"],
                encoding, tp_names=tuple(tp_specs))
        elif flat_state and self._updater_state:
            # idempotent: a state already raveled for this world size
            # and tp split passes through untouched (a residual left by
            # an encoded run is stripped — it belongs to that exchange)
            from deeplearning4j_tpu.parallel.zero import (
                strip_encoded_state, to_sharded_state)
            self._updater_state = to_sharded_state(
                var_vals, strip_encoded_state(self._updater_state),
                mesh.shape["data"], tp_names=tuple(tp_specs))
        elif not flat_state and (is_dp_sharded(self._updater_state)
                                 or has_tp(self._updater_state)
                                 or is_encoded(self._updater_state)):
            from deeplearning4j_tpu.parallel.zero import (
                strip_encoded_state, to_dense_state)
            self._updater_state = strip_encoded_state(
                to_dense_state(var_vals, self._updater_state))
        self._rng, rng = jax.random.split(self._rng)
        if mesh is not None:
            from deeplearning4j_tpu.parallel import replicate_tree
            if fsdp:
                # variables enter the flat resident layout: 1/N per
                # replica along the data axis for the whole fori window
                # (tp variables resident at their model(×data) spec)
                from deeplearning4j_tpu.learning.updaters import (
                    FSDP_KEY, TP_KEY, dp_ravel)
                from deeplearning4j_tpu.parallel.mesh import flat_sharding
                rest = {n: v for n, v in var_vals.items()
                        if n not in tp_specs}
                flats, _ = dp_ravel(rest, mesh.shape["data"],
                                    self._fsdp_spec)
                shard = flat_sharding(mesh, "data")
                vv = {FSDP_KEY: {dt: jax.device_put(v, shard)
                                 for dt, v in flats.items()}}
                if tp_specs:
                    from deeplearning4j_tpu.parallel.zero import \
                        place_tp_params
                    vv[TP_KEY] = place_tp_params(
                        mesh, {"v": {n: var_vals[n] for n in tp_specs}},
                        {"v": tp_specs}, resident=True)["v"]
                var_vals = vv
            elif tp_specs:
                # dense×tp / sharded×tp: tp variables live at their
                # compute sharding, the rest replicate
                from deeplearning4j_tpu.parallel.zero import \
                    place_tp_params
                var_vals = place_tp_params(
                    mesh, {"v": var_vals}, {"v": tp_specs})["v"]
            else:
                var_vals = replicate_tree(mesh, var_vals)
            if flat_state:
                # 1/N of the optimizer state per replica — the HBM win
                from deeplearning4j_tpu.parallel.zero import \
                    place_updater_states
                self._updater_state = place_updater_states(
                    mesh, {"state": self._updater_state},
                    tp_specs={"state": tp_specs})["state"]
            else:
                self._updater_state = replicate_tree(
                    mesh, self._updater_state)
            rng = replicate_tree(mesh, rng)
        from deeplearning4j_tpu.common import diagnostics, telemetry
        with telemetry.step_span("SameDiff", steps=n_steps) as sp:
            new_vars, self._updater_state, loss = multi_fn(
                var_vals, self._updater_state, ph_vals, rng,
                jnp.asarray(self.iteration_count), n_steps)
        if fsdp:
            # _arrays stay dense between calls (output()/getters read
            # them directly); the densify gather is timed into
            # dl4j_fsdp_gather_seconds
            from deeplearning4j_tpu.parallel.zero import params_to_dense
            new_vars = params_to_dense(
                {"vars": new_vars}, {"vars": self._fsdp_spec})["vars"]
        self._arrays.update(new_vars)
        self.iteration_count += n_steps
        diagnostics.after_step(self, "SameDiff",
                               self.iteration_count - 1, loss, sp,
                               params=new_vars, steps=n_steps)
        self._score = float(loss)
        first = next(iter(ph_vals.values()), None)
        if first is not None and first.ndim:
            self.last_batch_size = int(first.shape[0])
        # one listener round per fori group with the final loss (the
        # MLN fit_steps contract): checkpoints/score logging still
        # attach to the benchmark-grade loop
        for lis in self.listeners:
            lis.iteration_done(self, self.iteration_count - 1,
                               self.epoch_count)
        return self._score

    # -- listener bus (reference: SameDiff.setListeners; SURVEY S4/S8:
    # the same TrainingListener impls as MLN/graph) ---------------------
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def score(self) -> float:
        """Loss of the most recent train step (TrainingListener
        surface: ScoreIterationListener calls ``model.score()``)."""
        return float(self._score)

    def _run_validation(self, iterator, evaluations, placeholders_fn):
        """One pass over the validation iterator: mean loss + the
        requested per-output-var evaluations (reference: SameDiff.fit's
        validation ``History`` records)."""
        cfg = self.training_config
        evals = {}
        for name, spec in (evaluations or {}).items():
            factory, label_idx = (spec if isinstance(spec, tuple)
                                  else (spec, 0))
            evals[name] = (factory(), label_idx)
        if hasattr(iterator, "reset"):
            iterator.reset()
        data = iterator
        if hasattr(data, "features"):
            data = [data]
        losses, n = [], 0
        want = list(evals) + [v for v in self.loss_variables
                              if v not in evals]
        for batch in data:
            ph = (placeholders_fn(batch) if placeholders_fn
                  else cfg.placeholders_from(batch))
            out = self.output(ph, want)
            bl = sum(float(jnp.sum(out[v]))
                     for v in self.loss_variables)
            losses.append(bl)
            n += 1
            if not evals:
                continue     # loss-only validation: batches need not
            # labels come from the label-mapped placeholders when the
            # mapping names them (covers placeholders_fn dict batches),
            # else from the DataSet protocol       carry .labels at all
            if cfg.data_set_label_mapping and all(
                    n in ph for n in cfg.data_set_label_mapping):
                labels = [ph[n] for n in cfg.data_set_label_mapping]
            else:
                labels = getattr(batch, "labels", None)
                if labels is None:
                    raise ValueError(
                        "validation evaluation needs labels: map them "
                        "via data_set_label_mapping or provide "
                        "batches with a .labels attribute")
                labels = (labels if isinstance(labels, (list, tuple))
                          else [labels])
            for name, (e, li) in evals.items():
                e.eval(np.asarray(labels[li]), np.asarray(out[name]))
        val_loss = float(np.mean(losses)) if n else float("nan")
        return {k: e for k, (e, _) in evals.items()}, val_loss

    def fit(self, iterator=None, *, n_epochs: int = 1,
            placeholders_fn=None, listeners=None, validation_iter=None,
            validation_evaluations=None, validation_frequency: int = 1):
        """fit(MultiDataSetIterator-like). Each element must provide the
        placeholder dict via training_config's feature/label mappings
        (reference: TrainingConfig dataSetFeatureMapping), or supply
        ``placeholders_fn(batch) -> dict``.

        ``listeners``: extra TrainingListeners for this call (on top of
        ``set_listeners``'s) — Score/Performance/Evaluative/Checkpoint
        impls attach unchanged (the r4 verdict's S4 gap: imported
        models used to train blind).
        ``validation_iter`` + ``validation_evaluations``
        ({output_var: Evaluation-factory or (factory, label_index)}):
        evaluated every ``validation_frequency`` epochs; results land
        in the returned History's evaluation records."""
        from deeplearning4j_tpu.autodiff.training import (
            History, device_prefetch_placeholders)
        from deeplearning4j_tpu.common.environment import Environment
        cfg = self.training_config
        if cfg is None:
            raise ValueError("call set_training_config first")
        if not self.loss_variables:
            raise ValueError("call set_loss_variables first")
        all_listeners = self.listeners + list(listeners or [])
        history = History()
        step_fn = None
        trainable = None
        iteration = self.iteration_count
        env = Environment.get()

        def make_ph(batch):
            # host-side mapping only; the staging generator (or the
            # sync fallback below) owns the device conversion
            return (placeholders_fn(batch) if placeholders_fn
                    else cfg.placeholders_from(batch))

        for epoch in range(n_epochs):
            for lis in all_listeners:
                lis.on_epoch_start(self)
            if hasattr(iterator, "reset"):
                iterator.reset()
            epoch_losses = []
            # device-prefetch: make_ph + the H2D copies run on a feeder
            # thread a batch ahead of the step loop
            staged = (device_prefetch_placeholders(
                          iterator, make_ph,
                          depth=env.device_prefetch_depth)
                      if env.device_prefetch
                      else ({k: jnp.asarray(v)
                             for k, v in make_ph(b).items()}
                            for b in iterator))
            for ph_vals in staged:
                if self._retrace_guard is None:
                    from deeplearning4j_tpu.common.compilecache import \
                        RetraceGuard
                    self._retrace_guard = RetraceGuard(
                        "SameDiff train step")
                self._retrace_guard.record(
                    *(ph_vals[k] for k in sorted(ph_vals)))
                if step_fn is None:
                    # cache the COMPILED step across fit() calls: a
                    # fresh jax.jit wrapper per fit would recompile
                    # the whole program every call (measured 110x on
                    # imported BERT-base — BENCH_notes_r04.md)
                    key = tuple(sorted(ph_vals))
                    cached = self._exec_cache.get(("train", key))
                    if cached is None:
                        cached = self._build_train_step(
                            tuple(ph_vals))
                        self._exec_cache[("train", key)] = cached
                    step_fn, trainable = cached
                    if self._updater_state is None:
                        self._updater_state = cfg.updater.init_state(
                            {n: self._arrays[n] for n in trainable})
                        self._restore_updater_leaves()
                    self._updater_trainable = list(trainable)
                var_vals = {n: self._arrays[n] for n in trainable}
                from deeplearning4j_tpu.learning.updaters import \
                    is_dp_sharded
                if is_dp_sharded(self._updater_state):
                    # left over from a ZeRO-1 fit_steps(mesh=...) run;
                    # this dense step needs the slot-tree layout
                    from deeplearning4j_tpu.parallel.zero import \
                        to_dense_state
                    self._updater_state = to_dense_state(
                        var_vals, self._updater_state)
                self._rng, rng = jax.random.split(self._rng)
                from deeplearning4j_tpu.common import (diagnostics,
                                                       telemetry)
                with telemetry.step_span("SameDiff") as sp:
                    new_vars, self._updater_state, loss = step_fn(
                        var_vals, self._updater_state, ph_vals,
                        jnp.asarray(iteration), rng)
                self._arrays.update(new_vars)
                # loss-only watchdog (grads stay fused in the step);
                # a trip scans the just-updated variables for the
                # first poisoned leaf
                diagnostics.after_step(self, "SameDiff", iteration,
                                       loss, sp, params=new_vars)
                if self._frozen_captured_vars \
                        and self._frozen_captured_vars & set(new_vars):
                    # a NESTED subgraph froze one of the variables we
                    # just trained — its value is baked per compile,
                    # so drop BOTH compiled-program caches (output()
                    # programs and this loop's step_fn). Retrace per
                    # step is the price of freezing trainables into
                    # nested closures; thread them through loop args
                    # to avoid it.
                    self._exec_cache.clear()
                    step_fn = None
                epoch_losses.append(float(loss))
                self._score = epoch_losses[-1]
                first = next(iter(ph_vals.values()))
                self.last_batch_size = (int(first.shape[0])
                                        if first.ndim else 0)
                # advance the counter BEFORE listeners fire (the
                # MLN/fit_steps convention): an iteration-triggered
                # checkpoint must serialize the post-step count, so a
                # resumed job does not re-apply the consumed updater
                # index. Listeners get the just-consumed index and the
                # MODEL-lifetime epoch count, like MLN's bus.
                iteration += 1
                self.iteration_count = iteration
                for lis in all_listeners:
                    lis.iteration_done(self, iteration - 1,
                                       self.epoch_count)
            evals, val_loss = {}, float("nan")
            if validation_iter is not None and \
                    (epoch + 1) % max(1, validation_frequency) == 0:
                evals, val_loss = self._run_validation(
                    validation_iter, validation_evaluations,
                    placeholders_fn)
            history.add_epoch(epoch, epoch_losses, evals, val_loss)
            # epoch count advances BEFORE listeners fire (an epoch-end
            # checkpoint must serialize the true count — MLN contract)
            self.epoch_count += 1
            for lis in all_listeners:
                lis.on_epoch_end(self)
        return history

    def _restore_updater_leaves(self):
        """Graft updater leaves saved by ``save`` onto the freshly-built
        state tree (same graph + updater -> same treedef), so a loaded
        model resumes with its optimizer moments intact."""
        loaded = getattr(self, "_loaded_updater_leaves", None)
        if loaded is None:
            return
        leaves, treedef = jax.tree_util.tree_flatten(self._updater_state)
        if len(leaves) != len(loaded):
            raise ValueError(
                f"saved updater state has {len(loaded)} leaves, current "
                f"updater expects {len(leaves)} — updater/graph changed "
                f"since save")
        self._updater_state = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(l) for l in loaded])
        self._loaded_updater_leaves = None

    # -- serialization (S5) --------------------------------------------
    def save(self, path: str, save_updater_state: bool = True):
        """Zip: graph.json + arrays.npz (+ updater npz) — the same
        contract as the reference .fb (graph + params + updater state +
        training config)."""
        _write_samediff_zip(path,
                            *self._serialized_state(save_updater_state))

    def checkpoint_snapshot(self):
        """Host-side snapshot for the async CheckpointListener: every
        array is copied device->host NOW; ``write(path)`` can then run
        on a background thread while training keeps mutating this
        graph (the same contract as utils.checkpoint._ModelSnapshot
        for MLN/graph models)."""
        graph, arrays, cf_arrays, upd = self._serialized_state(True)

        class _Snap:
            def write(s, path):
                _write_samediff_zip(path, graph, arrays, cf_arrays, upd)
        return _Snap()

    def _serialized_state(self, save_updater_state: bool):
        cf_arrays: dict = {}   # control-flow subgraph constants/captures
        graph = {
            "variables": [
                {"name": v.name, "type": v.var_type.value,
                 "shape": list(v.shape) if v.shape else None,
                 "dtype": str(v.dtype) if v.dtype else None}
                for v in self.vars.values()],
            "ops": [{"op": o.op_name, "inputs": o.inputs,
                     "outputs": o.outputs,
                     "attrs": _json_attrs(o.attrs, cf_arrays,
                                          f"__cf.op{i}")}
                    for i, o in enumerate(self.ops)],
            "loss_variables": self.loss_variables,
            "training_config": (self.training_config.to_map()
                                if self.training_config else None),
            # resuming training must continue the updater iteration
            # (Adam bias correction) and the epoch schedule, not
            # restart either at 0
            "iteration_count": self.iteration_count,
            "epoch_count": self.epoch_count,
        }
        # np.array (copy), not np.asarray: on CPU the conversion is a
        # zero-copy VIEW of the XLA buffer, and fit donates var/updater
        # buffers — an executable honoring the donation would mutate a
        # checkpoint_snapshot while its background write is in flight
        arrays = {k: np.array(v) for k, v in self._arrays.items()}
        upd_leaves = None
        if save_updater_state and self._updater_state is not None:
            state = self._updater_state
            from deeplearning4j_tpu.learning.updaters import \
                is_dp_sharded
            if is_dp_sharded(state):
                # serialize the dense per-variable layout so the saved
                # leaf order/count is independent of mesh/shard count
                from deeplearning4j_tpu.parallel.zero import \
                    to_dense_state
                names = getattr(self, "_updater_trainable", ())
                state = to_dense_state(
                    {n: self._arrays[n] for n in names}, state)
            leaves, _ = jax.tree_util.tree_flatten(state)
            upd_leaves = [np.array(l) for l in leaves]
        return graph, arrays, cf_arrays, upd_leaves

    @staticmethod
    def load(path: str) -> "SameDiff":
        from deeplearning4j_tpu.autodiff.training import TrainingConfig
        sd = SameDiff()
        with zipfile.ZipFile(path) as z:
            graph = json.loads(z.read("graph.json"))
            arrays = np.load(io.BytesIO(z.read("arrays.npz")))
            arr_map = {k: jnp.asarray(arrays[k]) for k in arrays.files}
        for vd in graph["variables"]:
            v = SDVariable(sd, vd["name"], VariableType(vd["type"]),
                           vd["shape"], vd["dtype"])
            sd.vars[v.name] = v
            if v.name in arr_map:
                sd._arrays[v.name] = arr_map[v.name]
        for i, od in enumerate(graph["ops"]):
            node = OpNode(od["op"], od["inputs"], od["outputs"],
                          _rebuild_cf_attrs(od["op"], od["attrs"],
                                            arr_map))
            sd.ops.append(node)
            for on in node.outputs:
                sd._producer[on] = i
        sd.loss_variables = graph.get("loss_variables", [])
        sd.iteration_count = graph.get("iteration_count", 0)
        sd.epoch_count = graph.get("epoch_count", 0)
        tc = graph.get("training_config")
        if tc:
            sd.training_config = TrainingConfig.from_map(tc)
        with zipfile.ZipFile(path) as z:
            if "updater.npz" in z.namelist():
                upd = np.load(io.BytesIO(z.read("updater.npz")))
                sd._loaded_updater_leaves = [
                    upd[f"leaf_{i}"] for i in range(len(upd.files))]
        return sd

    # -- introspection -------------------------------------------------
    def variables(self) -> List[SDVariable]:
        return list(self.vars.values())

    def get_variable(self, name: str) -> SDVariable:
        return self.vars[name]

    def has_variable(self, name: str) -> bool:
        return name in self.vars

    def summary(self) -> str:
        lines = [f"{'var':<28}{'type':<14}{'shape':<18}producer op"]
        for v in self.vars.values():
            prod = ""
            if v.name in self._producer:
                prod = self.ops[self._producer[v.name]].op_name
            lines.append(f"{v.name:<28}{v.var_type.value:<14}"
                         f"{str(v.shape):<18}{prod}")
        lines.append(f"{len(self.ops)} ops, {len(self.vars)} variables")
        return "\n".join(lines)


def _json_attrs(attrs: dict, array_sink: Optional[dict] = None,
                prefix: str = "") -> dict:
    out = {}
    for k, v in (attrs or {}).items():
        if k == "rng" or callable(v):
            continue    # call closures are rebuilt from *_spec on load
        if k.endswith("_spec") and isinstance(v, dict) and "child" in v:
            v = _spec_to_json(v, array_sink, f"{prefix}.{k}")
        elif isinstance(v, (np.integer,)):
            v = int(v)
        elif isinstance(v, (np.floating,)):
            v = float(v)
        elif isinstance(v, tuple):
            v = list(v)
        elif hasattr(v, "dtype") and hasattr(v, "tolist"):
            v = v.tolist()
        out[k] = v
    return out


def _spec_to_json(spec: dict, array_sink: Optional[dict] = None,
                  prefix: str = "") -> dict:
    """Serialize a control-flow subgraph (see _trace_subgraph): child
    graph structure + constants, with frozen outer-graph captures baked
    to their save-time values (matching the runtime freeze semantics).
    Arrays go into ``array_sink`` (written to the zip's arrays.npz
    under ``prefix``) — large captured weights stay binary; without a
    sink they inline into the JSON (small graphs / tests)."""
    child = spec["child"]
    arrays = {n: np.asarray(a) for n, a in child._arrays.items()}
    for local, owner, pname in spec["frozen_caps"]:
        arrays[local] = np.asarray(owner._arrays[pname])
    out = {
        "vars": [{"name": v.name, "type": v.var_type.value,
                  "shape": list(v.shape) if v.shape else None,
                  "dtype": str(v.dtype) if v.dtype else None}
                 for v in child.vars.values()],
        "ops": [{"op": o.op_name, "inputs": o.inputs,
                 "outputs": o.outputs,
                 "attrs": _json_attrs(o.attrs, array_sink,
                                      f"{prefix}.op{i}")}
                for i, o in enumerate(child.ops)],
        "proxies": spec["proxies"],
        "outs": spec["outs"],
        "parent_cap_locals": spec["parent_cap_locals"],
    }
    if array_sink is not None:
        out["arrays_prefix"] = prefix
        out["array_names"] = sorted(arrays)
        for n, a in arrays.items():
            array_sink[f"{prefix}/{n}"] = a
    else:
        out["arrays"] = {n: {"dtype": str(a.dtype), "data": a.tolist()}
                         for n, a in arrays.items()}
    return out


def _call_from_json_spec(spec: dict, arr_map: Optional[dict] = None):
    """Rebuild a subgraph call closure from its serialized form (the
    load-side twin of _trace_subgraph's `call`). ``arr_map`` holds the
    zip's arrays.npz entries for npz-referenced specs."""
    child = SameDiff()
    for vd in spec["vars"]:
        v = SDVariable(child, vd["name"], VariableType(vd["type"]),
                       tuple(vd["shape"]) if vd["shape"] else None,
                       vd["dtype"])
        child.vars[v.name] = v
    if "arrays_prefix" in spec:
        pre = spec["arrays_prefix"]
        for n in spec["array_names"]:
            child._arrays[n] = jnp.asarray(arr_map[f"{pre}/{n}"])
    else:
        for n, rec in spec.get("arrays", {}).items():
            child._arrays[n] = jnp.asarray(
                np.asarray(rec["data"], dtype=rec["dtype"]))
    for i, od in enumerate(spec["ops"]):
        attrs = _rebuild_cf_attrs(od["op"], od["attrs"], arr_map)
        node = OpNode(od["op"], od["inputs"], od["outputs"], attrs)
        child.ops.append(node)
        for on in node.outputs:
            child._producer[on] = i
    idxs = child._ancestors(list(spec["outs"]))
    proxies = list(spec["proxies"])
    cap_locals = list(spec["parent_cap_locals"])
    outs = list(spec["outs"])
    n_args = len(proxies)

    def call(*args):
        values = dict(child._arrays)
        values.update(zip(proxies, args[:n_args]))
        values.update(zip(cap_locals, args[n_args:]))
        child._execute(values, idxs, None, False)
        return [values[n] for n in outs]

    return call


#: control-flow attrs: call-closure key -> serialized-spec key
_CF_CALL_SPECS = {"_cond_call": "_cond_spec", "_body_call": "_body_spec",
                  "_true_call": "_true_spec",
                  "_false_call": "_false_spec"}


def _rebuild_cf_attrs(op_name: str, attrs: dict,
                      arr_map: Optional[dict] = None) -> dict:
    """Recreate call closures for a (possibly nested) control-flow op
    loaded from JSON; no-op for ordinary ops."""
    if op_name not in ("while_loop", "cond", "scan"):
        return attrs
    attrs = dict(attrs)
    for call_key, spec_key in _CF_CALL_SPECS.items():
        spec = attrs.get(spec_key)
        if spec is not None and call_key not in attrs:
            attrs[call_key] = _call_from_json_spec(spec, arr_map)
    return attrs
