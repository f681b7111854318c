"""DevicePrefetcher + persistent compile cache tests (ISSUE 1).

Covers: overlap correctness (bit-identical results vs sync feeding),
donation-aliasing safety, mesh-sharded placement, reset/exhaustion,
feeder-thread exception propagation, the env off-switch, and the
persistent XLA compilation cache (entry created; a second process
compiling the same program HITS the cache)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.activations import Activation
from deeplearning4j_tpu.common.environment import Environment
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (DataSetIterator,
                                                   ListDataSetIterator)
from deeplearning4j_tpu.datasets.prefetch import (DevicePrefetcher,
                                                  maybe_device_prefetch)
from deeplearning4j_tpu.learning import Sgd
from deeplearning4j_tpu.lossfunctions import LossFunction
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.weights import WeightInit


def _mlp_conf(seed=42):
    return (NeuralNetConfiguration.Builder()
            .seed(seed)
            .updater(Sgd(1e-2))
            .weight_init(WeightInit.XAVIER)
            .list()
            .layer(DenseLayer(n_out=16, activation=Activation.RELU))
            .layer(OutputLayer(n_out=3,
                               loss_function=LossFunction.MCXENT,
                               activation=Activation.SOFTMAX))
            .set_input_type(InputType.feed_forward(4))
            .build())


def _batches(n=6, batch=32, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = rng.randn(batch, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, batch)]
        out.append(DataSet(x, y))
    return out


class _FailingIterator(DataSetIterator):
    """Raises from next() on the feeder thread after 2 good batches."""

    def __init__(self, good):
        super().__init__()
        self._good = good
        self._i = 0

    def reset(self):
        self._i = 0

    def has_next(self):
        return True

    def next(self):  # noqa: A003
        if self._i >= len(self._good):
            raise RuntimeError("ETL exploded")
        ds = self._good[self._i]
        self._i += 1
        return ds

    def batch(self):
        return self._good[0].num_examples()


class TestDevicePrefetcher:
    def test_yields_all_batches_in_order(self):
        data = _batches()
        pf = DevicePrefetcher(ListDataSetIterator(data), depth=2)
        seen = list(pf)
        assert len(seen) == len(data)
        for got, want in zip(seen, data):
            np.testing.assert_array_equal(np.asarray(got.features),
                                          want.features)

    def test_arrays_are_device_resident(self):
        data = _batches(n=2)
        pf = DevicePrefetcher(ListDataSetIterator(data), depth=2,
                              dtype=jnp.float32)
        ds = next(iter(pf))
        assert isinstance(ds.features, jax.Array)
        assert isinstance(ds.labels, jax.Array)
        assert ds.features.dtype == jnp.float32

    @pytest.mark.parametrize("thread_put", [False, True])
    def test_results_bit_identical_to_sync(self, thread_put):
        """Both put disciplines (consumer-side = CPU default,
        feeder-thread = accelerator default) change timing only."""
        data = _batches()
        net_sync = MultiLayerNetwork(_mlp_conf()).init()
        net_pf = MultiLayerNetwork(_mlp_conf()).init()
        net_sync.fit(ListDataSetIterator(data), n_epochs=2)
        net_pf.fit(DevicePrefetcher(ListDataSetIterator(data),
                                    dtype=net_pf._dtype,
                                    thread_put=thread_put), n_epochs=2)
        for a, b in zip(jax.tree_util.tree_leaves(net_sync.params),
                        jax.tree_util.tree_leaves(net_pf.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_donation_safety_batch_reusable(self):
        """Train-step funnels donate only params/states/updater state —
        a staged batch must survive the step and be re-feedable."""
        data = _batches(n=1)
        pf = DevicePrefetcher(ListDataSetIterator(data))
        ds = next(iter(pf))
        net = MultiLayerNetwork(_mlp_conf()).init()
        net.fit(ds)
        # a donated buffer would raise on access; re-fitting must work
        np.asarray(ds.features)
        net.fit(ds)
        assert np.isfinite(net.score())

    def test_mesh_sharded_placement(self):
        from conftest import require_devices
        require_devices(4)
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        mesh = make_mesh({"data": 4}, jax.devices()[:4])
        data = _batches(n=2, batch=32)
        pf = DevicePrefetcher(ListDataSetIterator(data), mesh=mesh)
        ds = next(iter(pf))
        sh = ds.features.sharding
        assert sh.spec[0] == "data"
        assert len(set(d for d in sh.device_set)) == 4

    def test_reset_and_exhaustion(self):
        data = _batches(n=4)
        pf = DevicePrefetcher(ListDataSetIterator(data), depth=2)
        assert len(list(pf)) == 4
        assert not pf.has_next()            # exhausted
        with pytest.raises(StopIteration):
            pf.next()
        pf.reset()                           # restartable
        assert len(list(pf)) == 4
        pf.reset()
        pf.next()
        pf.reset()                           # reset mid-stream
        assert len(list(pf)) == 4

    def test_feeder_exception_propagates(self):
        pf = DevicePrefetcher(_FailingIterator(_batches(n=2)), depth=2)
        with pytest.raises(RuntimeError, match="ETL exploded"):
            list(pf)

    def test_env_flag_off_switch(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_DEVICE_PREFETCH", "0")
        Environment.reset()
        try:
            it = ListDataSetIterator(_batches(n=2))
            assert maybe_device_prefetch(it) is it
        finally:
            Environment.reset()

    def test_maybe_wraps_iterators_only(self):
        Environment.reset()
        it = ListDataSetIterator(_batches(n=2))
        wrapped = maybe_device_prefetch(it)
        assert isinstance(wrapped, DevicePrefetcher)
        assert maybe_device_prefetch(wrapped) is wrapped
        plain = [1, 2, 3]
        assert maybe_device_prefetch(plain) is plain

    def test_async_base_is_unwrapped(self):
        """DevicePrefetcher subsumes the host-async rung: wrapping an
        AsyncDataSetIterator must not stack a second consumer thread
        on the async iterator's (possibly native) queue."""
        from deeplearning4j_tpu.datasets.iterators import \
            AsyncDataSetIterator
        data = _batches(n=3)
        base = ListDataSetIterator(data)
        pf = DevicePrefetcher(AsyncDataSetIterator(base))
        assert pf._base is base
        assert len(list(pf)) == 3

    def test_preprocessor_applied_on_feeder(self):
        class _Shift:
            def transform(self, ds):
                ds.features = np.asarray(ds.features) + 1.0

        data = _batches(n=2)
        base = ListDataSetIterator([DataSet(np.array(d.features),
                                            np.array(d.labels))
                                    for d in data])
        pf = DevicePrefetcher(base)
        pf.set_pre_processor(_Shift())
        got = next(iter(pf))
        np.testing.assert_allclose(np.asarray(got.features),
                                   data[0].features + 1.0)


class TestRetraceGuard:
    def test_warns_past_threshold(self, caplog):
        import logging
        from deeplearning4j_tpu.common.compilecache import RetraceGuard
        g = RetraceGuard("net", threshold=2)
        with caplog.at_level(logging.WARNING, "deeplearning4j_tpu"):
            for b in (1, 2, 3):
                g.record(np.zeros((b, 4)), None)
        assert g.n_signatures == 3
        assert any("distinct input signatures" in r.message
                   for r in caplog.records)
        # repeat signatures don't re-warn or re-count
        n = len(caplog.records)
        g.record(np.zeros((2, 4)), None)
        assert g.n_signatures == 3
        assert len(caplog.records) == n


_CACHE_CHILD = """
import sys, jax
import numpy as np
jax.config.update("jax_platforms", "cpu")
import deeplearning4j_tpu
from deeplearning4j_tpu.common import compilecache
print("CONFIGURED=%s" % compilecache.configure())
hits = []
from jax._src import monitoring
monitoring.register_event_listener(
    lambda ev, **kw: hits.append(ev))
from deeplearning4j_tpu.common.environment import Environment
Environment.reset()
from deeplearning4j_tpu.nn import (InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.weights import WeightInit
from deeplearning4j_tpu.learning import Sgd
from deeplearning4j_tpu.lossfunctions import LossFunction
from deeplearning4j_tpu.activations import Activation
conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(1e-2))
        .weight_init(WeightInit.XAVIER).list()
        .layer(DenseLayer(n_out=8, activation=Activation.RELU))
        .layer(OutputLayer(n_out=3,
                           loss_function=LossFunction.MCXENT,
                           activation=Activation.SOFTMAX))
        .set_input_type(InputType.feed_forward(4)).build())
net = MultiLayerNetwork(conf).init()
x = np.ones((8, 4), np.float32)
y = np.eye(3, dtype=np.float32)[np.zeros(8, int)]
net.fit(x, y)
print("CACHE_HITS=%d" %
      sum(1 for h in hits if h.endswith("cache_hits")))
"""


class TestPersistentCompileCache:
    def test_second_process_hits_cache(self, tmp_path):
        """The acceptance check, with the cache placed from outside:
        ``JAX_COMPILATION_CACHE_DIR`` names the directory, the package
        sets none, process 1 populates it and process 2 compiling the
        same network loads from it."""
        cache_dir = str(tmp_path / "xla-cache")
        env = {**os.environ,
               "JAX_COMPILATION_CACHE_DIR": cache_dir,
               # jax's own gates would skip this sub-second compile
               "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
               "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
               "JAX_PLATFORMS": "cpu"}
        env.pop("DL4J_TPU_COMPILE_CACHE", None)
        root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))

        def run():
            return subprocess.run(
                [sys.executable, "-c", _CACHE_CHILD], env=env,
                capture_output=True, text=True, timeout=300, cwd=root)

        r1 = run()
        assert r1.returncode == 0, r1.stderr[-2000:]
        assert "CONFIGURED=None" in r1.stdout, r1.stdout
        entries = os.listdir(cache_dir)
        assert any(e.endswith("-cache") for e in entries), entries
        r2 = run()
        assert r2.returncode == 0, r2.stderr[-2000:]
        hits = int(r2.stdout.strip().rsplit("CACHE_HITS=", 1)[1])
        assert hits > 0, (r2.stdout, r2.stderr[-2000:])

    def test_where_the_cache_lives(self):
        """The placement rule (common.compilecache.resolve_cache_dir):
        jax's variable wins and the package sets nothing; unset, the
        directory is <checkout>/.jax_cache; a process pinned to cpu
        gets none unless DL4J_TPU_COMPILE_CACHE=1; =0 opts out."""
        from deeplearning4j_tpu.common.compilecache import (
            checkout_cache_dir, resolve_cache_dir)
        root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        here = os.path.join(root, ".jax_cache")
        assert checkout_cache_dir() == here
        assert resolve_cache_dir(
            {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}, "tpu") is None
        assert resolve_cache_dir({}, "tpu") == here
        assert resolve_cache_dir({}, None) == here
        assert resolve_cache_dir({}, "cpu") is None
        assert resolve_cache_dir(
            {"DL4J_TPU_COMPILE_CACHE": "1"}, "cpu") == here
        assert resolve_cache_dir(
            {"DL4J_TPU_COMPILE_CACHE": "0"}, "tpu") is None
        # tier-1 runs pinned to cpu: this process placed no cache
        assert jax.config.jax_compilation_cache_dir is None or \
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
