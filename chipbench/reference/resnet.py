"""Plain reference for a bottleneck ResNet (He et al. 2015, table 1) trained with
softmax cross-entropy, L2 on the weights and Nesterov momentum: weights and batches
from the seed, forward, loss, gradients and the update in jax.numpy float32 at
``highest`` precision. Imports nothing of the program; vertex names are the
program's so that leaves can be matched by name."""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.decoder import key_of


def _sites(cfg):
    """(name, kernel, stride, cin, cout, relu) of every conv+BN pair, in order."""
    out = [("stem", 7, 2, cfg["channels"], 64, True)]
    cin = 64
    for si, (blocks, width) in enumerate(cfg["stages"]):
        for bi in range(blocks):
            s = 2 if (bi == 0 and si > 0) else 1
            n = f"s{si}b{bi}"
            out += [(f"{n}_a", 1, s, cin, width, True),
                    (f"{n}_b", 3, 1, width, width, True),
                    (f"{n}_c", 1, 1, width, 4 * width, False)]
            if bi == 0:
                out.append((f"{n}_sc", 1, s, cin, 4 * width, False))
            cin = 4 * width
    return out, cin


def generate(cfg, key):
    sites, feat = _sites(cfg)
    keys = jax.random.split(key, len(sites) + 1)
    p = {}
    for k, (name, ksz, _, cin, cout, _) in zip(keys, sites):
        std = (2.0 / (ksz * ksz * cin)) ** 0.5
        p[f"{name}_conv"] = {"W": jax.random.normal(
            k, (ksz, ksz, cin, cout), jnp.float32) * jnp.float32(std)}
        gamma = cfg["last_bn_gamma"] if name.endswith("_c") else 1.0
        p[f"{name}_bn"] = {"gamma": jnp.full((cout,), gamma), "beta": jnp.zeros((cout,))}
    p["output"] = {"W": jax.random.normal(keys[-1], (feat, cfg["num_classes"]),
                                          jnp.float32) * jnp.float32((2.0 / feat) ** 0.5),
                   "b": jnp.zeros((cfg["num_classes"],))}
    return p


def make_params(cfg, seed):
    return jax.jit(functools.partial(generate, cfg))(key_of(seed))


def make_batches(cfg, seed, n, batch):
    """``n`` batches of images ``[n, batch, h, w, c]`` and one-hot labels, every
    row different, made on the device in one call."""
    def gen(key):
        kx, ky = jax.random.split(jax.random.fold_in(key, 7))
        x = jax.random.normal(kx, (n, batch, cfg["height"], cfg["width"],
                                   cfg["channels"]), jnp.float32)
        y = jax.random.randint(ky, (n, batch), 0, cfg["num_classes"])
        return x, jax.nn.one_hot(y, cfg["num_classes"], dtype=jnp.float32)
    return jax.jit(gen)(key_of(seed))


def _fp8(x):
    """Round to float8_e4m3 under a per-tensor scale; gradient passes straight."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    r = (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale
    return x + lax.stop_gradient(r - x)


def loss_fn(cfg, params, x, y, low=False):
    q = _fp8 if low else (lambda a: a)

    def conv_bn(name, x, stride, relu):
        z = q(lax.conv_general_dilated(
            q(x), q(params[f"{name}_conv"]["W"]), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")))
        mean = jnp.mean(z, (0, 1, 2))
        var = jnp.mean(jnp.square(z - mean), (0, 1, 2))
        bn = params[f"{name}_bn"]
        z = (z - mean) / jnp.sqrt(var + cfg["bn_eps"]) * bn["gamma"] + bn["beta"]
        return q(jax.nn.relu(z) if relu else z)

    @jax.checkpoint
    def stem(x):
        x = conv_bn("stem", x, 2, True)
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")

    x = stem(x)
    for si, (blocks, _) in enumerate(cfg["stages"]):
        for bi in range(blocks):
            n, s = f"s{si}b{bi}", (2 if (bi == 0 and si > 0) else 1)

            @jax.checkpoint
            def block(x, n=n, s=s, first=(bi == 0)):
                a = conv_bn(f"{n}_a", x, s, True)
                b = conv_bn(f"{n}_b", a, 1, True)
                c = conv_bn(f"{n}_c", b, 1, False)
                sc = conv_bn(f"{n}_sc", x, s, False) if first else x
                return q(jax.nn.relu(c + sc))
            x = block(x)
    z = q(jnp.mean(x, (1, 2))) @ q(params["output"]["W"]) + params["output"]["b"]
    data = -jnp.mean(jnp.sum(y * jax.nn.log_softmax(z), -1))
    l2 = sum(jnp.sum(jnp.square(v["W"])) for v in params.values() if "W" in v)
    return data + 0.5 * cfg["l2"] * l2


def leaf_norms(tree):
    """``{"vertex/leaf": norm}`` as one array in sorted-name order, with names."""
    flat = {f"{v}/{k}": a for v, sub in tree.items() for k, a in sub.items()}
    names = sorted(flat)
    return names, jnp.stack([jnp.sqrt(jnp.sum(jnp.square(flat[n].astype(jnp.float32))))
                             for n in names])


def first_steps(cfg, params, xs, ys, low=False, rows=None):
    """Follow ``len(xs)`` steps of Nesterov momentum from ``params``. Returns each
    step's loss, the first gradient with its per-leaf norms, and the per-leaf norm of
    the parameters' change over all the steps. ``rows`` plants the fault of a batch cut to its
    first rows; ``low`` is the control: every tensor the configuration computes in
    bfloat16 (convolution inputs and outputs, batch norm's output, the residual sum) is
    held in float8_e4m3 instead."""
    lr, mu = cfg["learning_rate"], cfg["momentum"]

    @jax.jit
    def step(p, v, x, y):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(functools.partial(loss_fn, cfg))(
                p, x[:rows], y[:rows], low=low)
        v = jax.tree_util.tree_map(lambda v_, g_: mu * v_ - lr * g_, v, g)
        p = jax.tree_util.tree_map(lambda p_, v_, g_: p_ + mu * v_ - lr * g_, p, v, g)
        return loss, g, p, v

    p, v = params, jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad = [], None
    for x, y in zip(xs, ys):
        loss, g, p, v = step(p, v, x, y)
        losses.append(float(loss))
        grad = g if grad is None else grad
    gnorm = leaf_norms(grad)[1]
    names, dnorm = leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, p, params))
    return {"loss": losses, "names": names, "grad": grad,
            "gnorm": jax.device_get(gnorm), "dnorm": jax.device_get(dnorm)}
