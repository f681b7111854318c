"""The rehearsals that cost no chip time, in one command (``python -m chipbench.rehearse``):

1. every kind of cell end to end at a tiny size on the CPU (Pallas in interpret mode),
   from a copy of the benchmark to which the tiny configurations, mixes and cells are
   added as new files and entries only;
2. with ``--aot``, the plain references' step programs compiled at the real size for a
   described ``v5e:2x2`` chip, printing ``memory_analysis()``.

Nothing it prints is a device number."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

TINY = {
    "resnet-tiny": ("resnet50", {"num_classes": 10, "height": 32, "width": 32,
                                 "stages": [[1, 8], [1, 16]]}),
    "decoder-tiny": ("gpt2-large", {"vocab_size": 96, "n_layers": 2, "n_heads": 2, "d_model": 32,
                                    "d_ff": 64, "max_len": 64, "max_in_flight": 4,
                                    "engine": {"kv_blocks": 17, "kv_block_size": 16, "kv_dtype": "bf16",
                                               "prompt_buckets": [16, 32], "decode_buckets": [4],
                                               "max_seq_len": 64, "paged": True}}),
}
MIXES = {
    "train-tiny": {"kind": "steps", "batch_per_chip": 8, "ring": 4},
    "closed-tiny": {"kind": "closed", "clients": 4,
                    "prompt": {"dist": "uniform", "lo": 4, "hi": 24},
                    "output": {"dist": "uniform", "lo": 6, "hi": 24}},
}
CELLS = {"resnet-tiny.train-tiny": {"train_samples_per_s": "samples/s"},
         "decoder-tiny.closed-tiny": {"gen_tok_per_s": "tokens/s", "itl_p95_ms": "ms"}}


def tiny_copy(dest):
    """A copy of the benchmark with the tiny cells added: new files and entries only."""
    shutil.rmtree(dest, ignore_errors=True)
    shutil.copytree(HERE, os.path.join(dest, "chipbench"),
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, (base, changes) in TINY.items():
        with open(os.path.join(HERE, "configs", f"{base}.json")) as f:
            cfg = dict(json.load(f), name=name, **changes)
        with open(os.path.join(dest, "chipbench", "configs", f"{name}.json"), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": name, "source": cfg["source"], "reduced": [],
                                 "file": f"chipbench/configs/{name}.json", "why": "tiny"})
    for name, mix in MIXES.items():
        with open(os.path.join(dest, "chipbench", "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    known = {m["name"]: m for m in bench["end_to_end"]}
    for cell, metrics in CELLS.items():
        config, mix = cell.split(".")
        bench["workloads"].append({"name": cell, "config": config, "traffic": mix,
                                   "chips": 1, "why": "tiny"})
        for name, unit in metrics.items():
            if name not in known:
                known[name] = {"name": name, "unit": unit, "bound": 0.1, "source": "host_clock",
                               "better": "lower" if unit == "ms" else "higher", "workloads": []}
                bench["end_to_end"].append(known[name])
            known[name]["workloads"].append(cell)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


def run_cell(copy, cell, seed=3, seconds=2.0, patch=""):
    """One run of a cell of the copy, on the CPU, without the look for a chip."""
    code = (f"{patch}\nimport json\nfrom chipbench import harness\n"
            f"print(json.dumps(harness.measure({cell!r}, {seed}, {seconds}, 0, require_chip=False)))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                       capture_output=True, text=True, timeout=900)
    if p.returncode:
        raise RuntimeError(p.stderr[-3000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def aot():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench.reference import resnet
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    with open(os.path.join(HERE, "configs", "resnet50.json")) as f:
        cfg = json.load(f)
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)  # noqa: E731
    params = jax.tree_util.tree_map(shape, jax.eval_shape(lambda: resnet.generate(cfg, jax.random.PRNGKey(0))))
    x = jax.ShapeDtypeStruct((256, 224, 224, 3), jnp.float32, sharding=chip)
    y = jax.ShapeDtypeStruct((256, 1000), jnp.float32, sharding=chip)

    def grads(p, x, y):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda p: resnet.loss_fn(cfg, p, x, y))(p)
    print("resnet reference, b256, one v5e chip:", jax.jit(grads).lower(params, x, y).compile().memory_analysis())


if __name__ == "__main__":
    if "--aot" in sys.argv:
        aot()
    else:
        copy = tiny_copy(os.path.join(HERE, ".out", "rehearse"))
        for cell in CELLS:
            out = run_cell(copy, cell)
            print(cell, "correct" if out["correct"] else "NOT CORRECT", json.dumps(out))
