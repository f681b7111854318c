"""The system under test for a ResNet configuration: ``models.zoo.ResNet50`` behind
``ComputationGraph.fit``, with the benchmark's own weights and batches from the seed."""
import gc

import jax
import jax.numpy as jnp

from chipbench.reference import resnet as ref


class Program:
    def __init__(self, cfg, mix, seed, chips):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.models.zoo import ResNet50
        self.cfg, self.lr = cfg, cfg["learning_rate"]
        self.samples_per_step = mix["batch_per_chip"] * chips
        net = ResNet50(num_classes=cfg["num_classes"], height=cfg["height"],
                       width=cfg["width"], channels=cfg["channels"],
                       compute_dtype=cfg["compute_dtype"],
                       STAGES=tuple(tuple(s) for s in cfg["stages"])).init()
        up = net.conf.updater
        stated = (cfg["learning_rate"], cfg["momentum"])
        if (type(up).__name__, up.learning_rate, up.momentum) != ("Nesterovs",) + stated:
            raise RuntimeError(f"the program's updater {up} is not the configuration's")
        params = ref.make_params(cfg, seed)
        if jax.tree_util.tree_structure(params) != jax.tree_util.tree_structure(
                {k: v for k, v in net.params.items() if v}):
            raise RuntimeError("the program's parameter tree is not the reference's")
        net.params = {k: params.get(k, v) for k, v in net.params.items()}
        xs, ys = ref.make_batches(cfg, seed, mix["ring"], self.samples_per_step)
        self.ring = [DataSet(x, y) for x, y in zip(xs, ys)]
        self.net = net

    def step(self, i):
        """One step through the program's own entry; the loss, still on the device."""
        self.net.fit(self.ring[i % len(self.ring)])
        return self.net._score

    def wait(self):
        jax.block_until_ready(self.net.params)

    def params_copy(self):
        return jax.tree_util.tree_map(jnp.copy, {k: v for k, v in self.net.params.items() if v})

    def first_grad(self):
        """The first gradient as the updater got it, a copy: after one Nesterov step
        from rest its velocity is ``-lr * g``."""
        v = {k: s["v"] for k, s in self.net.updater_states.items() if s and s["v"]}
        return jax.tree_util.tree_map(lambda a: a / -self.lr, v)

    def change_norms(self, before):
        now = {k: v for k, v in self.net.params.items() if v}
        return ref.leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, now, before))

    def compiles_in_window(self):
        return self.net._retrace_guard.n_signatures - 1

    def close(self):
        self.net = self.ring = None
        gc.collect()


def build(cfg, mix, seed, chips):
    return Program(cfg, mix, seed, chips)
