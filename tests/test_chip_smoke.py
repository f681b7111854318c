"""chip_smoke.py on the CPU: the phases at tiny sizes with the kernels
in interpret mode, the entry point's refusal to run without a TPU, and
where the compile cache lives. The real run is ``python chip_smoke.py``
on the chip; this keeps the script from rotting between chip runs."""
import os
import subprocess
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY_STAGES = ((1, 8),)
TINY_LM = dict(vocab_size=64, n_layers=2, n_heads=2, d_model=32,
               d_ff=64, max_len=64)


def _run(code_or_script, env_extra, *, script=False):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "DL4J_TPU_COMPILE_CACHE", "XLA_FLAGS")}
    env.update(env_extra)
    cmd = [sys.executable] + ([code_or_script] if script
                              else ["-c", code_or_script])
    return subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True,
                          text=True, timeout=120)


class TestEntryPoint:
    def test_cpu_only_process_exits_before_compiling(self):
        r = _run(os.path.join(ROOT, "chip_smoke.py"),
                 {"JAX_PLATFORMS": "cpu", "JAX_LOG_COMPILES": "1"},
                 script=True)
        assert r.returncode == 2, (r.returncode, r.stderr[-2000:])
        assert "platform='cpu'" in r.stderr
        assert r.stdout == ""                 # no result line
        assert "Compiling" not in r.stderr    # nothing was compiled


class TestCacheRule:
    CHILD = ("import jax, deeplearning4j_tpu\n"
             "from deeplearning4j_tpu.common import compilecache\n"
             "print('DIR=%s' % jax.config.jax_compilation_cache_dir)\n"
             "print('SET=%s' % compilecache.configure())\n")

    def test_jax_variable_set_means_no_directory_set_in_code(self, tmp_path):
        r = _run(self.CHILD, {"JAX_PLATFORMS": "tpu",
                              "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        assert r.returncode == 0, r.stderr[-2000:]
        assert f"DIR={tmp_path}" in r.stdout      # jax's own reading
        assert "SET=None" in r.stdout

    def test_unset_means_checkout_jax_cache(self):
        r = _run(self.CHILD, {"JAX_PLATFORMS": "tpu"})
        assert r.returncode == 0, r.stderr[-2000:]
        here = os.path.join(ROOT, ".jax_cache")
        assert f"DIR={here}" in r.stdout
        assert f"SET={here}" in r.stdout
        with open(os.path.join(ROOT, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()


class TestPhasesTiny:
    def test_native(self):
        assert chip_smoke.phase_native()["ok"]

    def test_train_then_serve(self):
        r = chip_smoke.phase_train(
            batch=4, hw=16, classes=10, stages=TINY_STAGES,
            fit_calls=2, burst=2, steps=2)
        # auto_dense at every site, then the kernels by the force rung
        assert r["ok"] and r["bn_sites"] == 5
        assert r["mosaic_calls"] == 0         # interpret mode
        assert chip_smoke.phase_serve(
            r["net"], hw=16, n_clients=3, rows=(1, 2), buckets=(8,),
            force_kernels=True)["ok"]

    def test_generate(self):
        r = chip_smoke.phase_generate(
            TINY_LM, n_requests=2, max_tokens=6, prompt_len=4,
            kv_blocks=16, kv_block_size=8, max_seq_len=32,
            prompt_bucket=8, paged=True)
        assert r["ok"] and all(1 <= n <= 6 for n in r["tokens"])

    def test_hybrid(self):
        r = chip_smoke.phase_hybrid(
            dict(chip_smoke.H1_SMALL, vocab_size=96, hidden_size=40,
                 head_dim=8, intermediate_size=64, mamba_d_ssm=32,
                 mamba_d_head=8, mamba_d_state=16, init_std=0.3),
            gqa=(3, 10, 2, 16, 12, 8, 3), state=(2, 4, 4, 8, 16, 2, 3),
            prompt_len=11, prompt_bucket=16, steps=4, block=8)
        assert r["ok"] and r["model"] < 2e-2

    def test_kernels(self):
        r = chip_smoke.phase_kernels(
            epilogue_rows=256, epilogue_k=16, epilogue_n=128,
            bn_shapes=((2, 4, 4, 8), (3, 5, 5, 16)),
            flash_shape=(1, 2, 128, 16), paged=(2, 2, 16, 16, 8, 4),
            force_kernels=True)
        assert r["ok"]

    def test_four_chip_on_the_virtual_mesh(self):
        from tests.conftest import require_devices
        require_devices(4)
        r = chip_smoke.phase_four_chip(
            batch=8, hw=16, classes=10, stages=TINY_STAGES, steps=2,
            n=4)
        assert r["ok"] and len(r["losses"]) == 2
        assert jax.default_backend() == "cpu"
