"""Device-mesh construction and sharding helpers.

The reference binds parallelism to explicit device lists and per-device
model replicas (``ParallelWrapper`` workers, `org.deeplearning4j.
parallelism.factory.TrainerContext`). On TPU the analogue is a
``jax.sharding.Mesh`` with named axes; replication/sharding is expressed
as `NamedSharding` partition specs and the GSPMD partitioner inserts the
collectives (psum over ICI for the gradient all-reduce).

Axis convention (scaling-book style):
- ``data``  — batch dimension (DP); always present.
- ``model`` — tensor-parallel dimension (TP, megatron-style splits).
- ``seq``   — sequence/context-parallel dimension (SP/CP, ring attention).
- ``stage`` — pipeline stages (PP).
Axes of size 1 are free, so a single mesh shape covers every strategy.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_DATA_AXIS = "data"
DEFAULT_MODEL_AXIS = "model"
DEFAULT_SEQ_AXIS = "seq"
DEFAULT_STAGE_AXIS = "stage"


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh. ``axes`` maps axis name -> size; a single ``-1``
    entry absorbs the remaining devices (like a reshape). Default:
    all devices on the ``data`` axis (pure DP — the reference's only
    in-node strategy, SURVEY.md P1)."""
    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {DEFAULT_DATA_AXIS: len(devices)}
    names = list(axes.keys())
    sizes = list(axes.values())
    n = len(devices)
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} "
                         f"devices, have {n}")
    arr = np.array(devices).reshape(sizes)
    return Mesh(arr, axis_names=tuple(names))


class MeshFactory:
    """Named mesh presets (the counterpart of the reference's
    `TrainerContext` strategy selection)."""

    @staticmethod
    def data_parallel(n: Optional[int] = None) -> Mesh:
        devs = jax.devices()[:n] if n else jax.devices()
        return make_mesh({DEFAULT_DATA_AXIS: len(devs)}, devs)

    @staticmethod
    def data_model(data: int = -1, model: int = 1) -> Mesh:
        return make_mesh({DEFAULT_DATA_AXIS: data,
                          DEFAULT_MODEL_AXIS: model})

    @staticmethod
    def full(data: int = -1, model: int = 1, seq: int = 1,
             stage: int = 1) -> Mesh:
        return make_mesh({DEFAULT_DATA_AXIS: data,
                          DEFAULT_MODEL_AXIS: model,
                          DEFAULT_SEQ_AXIS: seq,
                          DEFAULT_STAGE_AXIS: stage})


def data_sharding(mesh: Mesh, ndim: int,
                  axis: str = DEFAULT_DATA_AXIS) -> NamedSharding:
    """Leading-axis (batch) sharding: P(data, None, ...)."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def flat_sharding(mesh: Mesh,
                  axis: str = DEFAULT_DATA_AXIS) -> NamedSharding:
    """1-D sharding along ``axis`` — the ZeRO-1 flat param/optimizer
    state layout (``parallel.zero``): each replica holds 1/N of the
    padded flat vector."""
    return NamedSharding(mesh, P(axis))


def replicate_tree(mesh: Mesh, tree):
    """Place every leaf fully replicated on the mesh (params/opt state
    for DP — the analogue of ParallelWrapper's per-device model copies,
    except there is ONE logical copy and XLA keeps replicas in sync)."""
    sh = replicated(mesh)
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, sh) if hasattr(a, "shape") else a,
        tree)


def shard_batch(mesh: Mesh, tree, axis: str = DEFAULT_DATA_AXIS):
    """Shard every array leaf along its leading (batch) dimension."""
    def put(a):
        if not hasattr(a, "ndim") or a.ndim == 0:
            return a
        return jax.device_put(a, data_sharding(mesh, a.ndim, axis))
    return jax.tree_util.tree_map(put, tree)


#: every batch-dim array attribute a DataSet/MultiDataSet can carry
#: (singular = DataSet, plural = MultiDataSet)
DATASET_ARRAY_ATTRS = ("features", "labels", "features_mask",
                       "labels_mask", "features_masks", "labels_masks")


def map_dataset_arrays(ds, fn):
    """Shallow-copy ``ds`` with ``fn`` applied to every array attribute
    (lists mapped elementwise, None passed through). The single place
    that knows the DataSet/MultiDataSet array surface — used by both the
    single-host and multi-host sharding paths."""
    import copy
    out = copy.copy(ds)
    for attr in DATASET_ARRAY_ATTRS:
        if not hasattr(ds, attr):
            continue
        v = getattr(ds, attr)
        if v is None:
            continue
        if isinstance(v, (list, tuple)):
            setattr(out, attr, [fn(x) if x is not None else None
                                for x in v])
        else:
            setattr(out, attr, fn(v))
    return out


def pad_batch_to_multiple(x, n: int):
    """Pad the leading axis up to a multiple of ``n`` by repeating the
    final example; returns (padded, original_size). Training callers
    should instead trim (padding would bias gradients); inference
    callers pad then slice the output back."""
    import jax.numpy as jnp
    b = x.shape[0]
    rem = b % n
    if rem == 0:
        return x, b
    pad = n - rem
    reps = jnp.repeat(x[-1:], pad, axis=0)
    return jnp.concatenate([x, reps], axis=0), b


def shard_map(f, mesh: Mesh, *, in_specs, out_specs, check_vma=True):
    """``jax.shard_map`` over ``mesh`` — the package-public seam every
    parallel module builds on.

    ``check_vma=False`` disables the static varying-manual-axes check —
    callers that opt out take over the cross-rank gradient reduction
    themselves and must say why at the call site."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis: str) -> int:
    """Concrete size of a mesh axis from inside shard_map tracing
    (the mesh is static, so this is a Python int)."""
    from jax import lax
    return int(lax.axis_size(axis))
