"""Numeric gradient checking (SURVEY.md §4.5:
`org.deeplearning4j.gradientcheck.GradientCheckUtil`).

Central-difference numeric gradients vs the analytic gradients the
jitted train path computes, parameter-by-parameter. Like the
reference, the check runs in DOUBLE precision — `jax.experimental.
enable_x64` scopes f64 to the check (training itself stays f32/bf16)
— so tolerances stay tight and f32 loss quantization can't mask or
fake a mismatch. What it validates: that every layer's backward
composition matches its forward (wrong masking, stop-gradients,
state handling...).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _net_loss_fn(net, ds):
    """loss(params) for a MultiLayerNetwork/ComputationGraph on one
    batch, deterministic (no dropout rng, training-mode forward)."""
    multi = hasattr(net, "conf") and hasattr(net.conf, "layers")

    if multi:
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        out_layer = net.output_layer_conf

        def loss(params):
            out, _ = net._forward(params, net.states, x,
                                  training=True, rng=None,
                                  want_logits=out_layer.wants_logits())
            return (out_layer.compute_loss(
                y, out, from_logits=out_layer.wants_logits())
                + net._regularization(params))
        return loss

    xs = [jnp.asarray(f) for f in (ds.features if isinstance(
        ds.features, list) else [ds.features])]
    ys = [jnp.asarray(l) for l in (ds.labels if isinstance(
        ds.labels, list) else [ds.labels])]
    out_confs = net.output_layer_confs()

    def loss(params):
        acts, _ = net._forward(params, net.states, xs, training=True,
                               rng=None, want_logits=True)
        total = net._regularization(params)
        for i, name in enumerate(net.conf.network_outputs):
            layer = out_confs.get(name)
            if layer is None:
                continue
            total = total + layer.compute_loss(
                ys[i], acts[name], from_logits=layer.wants_logits())
        return total
    return loss


class GradientCheckUtil:
    @staticmethod
    def check_gradients(net, ds, epsilon: float = 1e-5,
                        max_rel_error: float = 1e-4,
                        min_abs_error: float = 1e-8,
                        max_params_per_array: int = 16,
                        seed: int = 0,
                        print_results: bool = False) -> bool:
        """True iff every sampled parameter's numeric gradient matches
        the analytic one (relative error under ``max_rel_error``, with
        ``min_abs_error`` absorbing float32 noise near zero).

        ``max_params_per_array`` random entries are checked per
        parameter tensor (sampling keeps runtime sane with identical
        detection power for systematic backward bugs)."""
        with jax.enable_x64():
            return GradientCheckUtil._check_f64(
                net, ds, epsilon, max_rel_error, min_abs_error,
                max_params_per_array, seed, print_results)

    @staticmethod
    def _check_f64(net, ds, epsilon, max_rel_error, min_abs_error,
                   max_params_per_array, seed, print_results) -> bool:
        f64 = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a, np.float64))
            if np.issubdtype(np.asarray(a).dtype, np.floating) else a,
            t)
        params64 = f64(net.params)
        states_save = net.states
        cd_save = net.conf.compute_dtype
        try:
            net.states = f64(net.states)
            # mixed precision must be OFF for the check: _forward
            # would cast the promoted f64 values back down to bf16,
            # reducing the comparison to bf16 rounding noise
            net.conf.compute_dtype = None
            from deeplearning4j_tpu.parallel.mesh import \
                map_dataset_arrays

            def to64(a):
                a = np.asarray(a)
                return a.astype(np.float64) if np.issubdtype(
                    a.dtype, np.floating) else a
            ds = map_dataset_arrays(ds, to64)
            loss_fn = _net_loss_fn(net, ds)
            analytic = jax.grad(loss_fn)(params64)
            rng = np.random.RandomState(seed)
            flat_p, treedef = jax.tree_util.tree_flatten(params64)
            flat_g = jax.tree_util.tree_leaves(analytic)
            failures = []
            checked = 0
            for ai, (p, g) in enumerate(zip(flat_p, flat_g)):
                p_np = np.asarray(p, np.float64)
                g_np = np.asarray(g, np.float64)
                n = p_np.size
                idxs = (range(n) if n <= max_params_per_array else
                        rng.choice(n, max_params_per_array,
                                   replace=False))
                for flat_i in idxs:
                    delta = np.zeros_like(p_np).reshape(-1)
                    delta[flat_i] = epsilon
                    delta = delta.reshape(p_np.shape)

                    def at(offset):
                        newp = jax.tree_util.tree_unflatten(
                            treedef, [jnp.asarray(p_np + offset)
                                      if j == ai else q
                                      for j, q in enumerate(flat_p)])
                        return float(loss_fn(newp))

                    numeric = (at(delta) - at(-delta)) / (2 * epsilon)
                    ana = g_np.reshape(-1)[flat_i]
                    abs_err = abs(numeric - ana)
                    denom = max(abs(numeric), abs(ana))
                    rel = abs_err / denom if denom > 0 else 0.0
                    checked += 1
                    if rel > max_rel_error and abs_err > min_abs_error:
                        failures.append((ai, int(flat_i), float(ana),
                                         float(numeric), float(rel)))
        finally:
            net.states = states_save
            net.conf.compute_dtype = cd_save
        if print_results or failures:
            print(f"GradientCheckUtil: {checked} params checked, "
                  f"{len(failures)} failures")
            for f in failures[:10]:
                print("  array %d idx %d analytic %.6g numeric %.6g "
                      "rel %.3g" % f)
        return not failures
