"""Counts the instruction bundles of the paged decode kernel
(``ops/attention_pallas.py::_paged_call``) as the chip's compiler schedules it, at the
five shapes the served cells launch it with. No chip is attached and nothing runs or is
timed: the kernel is lowered and compiled for a described ``v5e:2x2`` by the installed
libtpu (``tests/test_v5e_compile.py`` does the same), which is asked for its dumps:

    PYTHONPATH=. JAX_PLATFORMS=cpu python scripts/probe_paged_bundles.py [shape,shape,...]

A shape compiles in a process of its own (with the dump flags the process aborts once the
dumps are written). ``*pallas.paged_decode_attention*final_hlo-static-per-bundle-
utilization.txt`` has one line a VLIW bundle of the kernel with the units it uses, and
``*final_bundles.txt`` the same bundles as text with their control targets. One JSON line
a shape: ``bundles``; ``once`` (to the end of the first predicated region of the grid's
loop, the ``i == 0`` branch: what a launch runs once, the pipeline's prologue with it);
the rest, which every sequence runs, as ``before`` the first bundle that uses the MXU,
``between`` the first and the last and ``after`` the last; ``per_row`` (their sum) and
``spill_stores`` (bundles with a vector spill store). Run it on another tree by putting
that tree first on ``PYTHONPATH``. Counts of a schedule, never a time."""
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

#: name: (rows, query heads, head_dim, K lanes, V lanes, pool blocks, table blocks,
#: layers, v_group, sink): the decode bucket, pool and table of each served cell
SHAPES = {
    "mimo-v2.5.window": (128, 64, 192, 1536, 1024, 129 * 8, 8, 5, 1, True),
    "mimo-v2.5.full": (128, 64, 192, 768, 512, 22529, 176, 2, 1, False),
    "phi4-mini-flash": (64, 40, 64, 1280, 1280, 8193, 128, 1, 2, False),
    "gpt2-large": (32, 20, 64, 1280, 1280, 385, 64, 36, 1, False),
    "falcon-h1-34b": (32, 20, 128, 512, 512, 3073, 96, 6, 1, False),
}
BLOCK = 16
_COLUMNS = ("MXU", "VSTORE:SPILL")


def compile_one(name):
    """The child: lower and compile ``_paged_call`` at ``name``'s shapes for the
    described chip (the dumps go where ``LIBTPU_INIT_ARGS`` says)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deeplearning4j_tpu.ops import attention_pallas as ap

    b, h, d, hd, hdv, blocks, table, layers, v_group, sink = SHAPES[name]
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    args = [of((b, h, d), jnp.float32),
            of((layers, blocks, BLOCK, hd), jnp.bfloat16),
            of((layers, blocks, BLOCK, hdv), jnp.bfloat16),
            of((b, table), jnp.int32), of((b,), jnp.int32), of((1,), jnp.int32)]
    if sink:
        args.append(of((h,), jnp.float32))
    ap._paged_call.lower(
        *args, scale=d ** -0.5, interpret=False, v_group=v_group,
        per_step=ap._paged_blocks_per_step(BLOCK, hd, 2, table)).compile()


def _one(pattern):
    found = glob.glob(pattern)
    if len(found) != 1:
        raise SystemExit(f"expected one file {pattern}, found {len(found)}")
    return found[0]


def read_dump(folder):
    """The counts of one compiled kernel from its two dump files."""
    stem = os.path.join(folder, "*pallas.paged_decode_attention*")
    lines = open(_one(stem + "final_hlo-static-per-bundle-utilization.txt")
                 ).read().splitlines()
    units = [u.strip() for u in lines[1].split(",")]
    mxu, spill = (units.index(c) for c in _COLUMNS)
    use = [[int(n) for n in ln.split()]
           for ln in lines[lines.index("== UTILIZATION:") + 1:] if ln.strip()]
    # the fallthrough of the first predicated region at the depth of the grid's loop
    once = 0
    for ln in open(_one(stem + "[0-9]-final_bundles.txt")):
        at = re.match(r"\s*(0x[0-9a-f]+|\d+)\s+PF: > \{", ln)
        if at:
            once = int(at.group(1), 0)
            break
    with_mxu = [n for n, u in enumerate(use) if u[mxu] and n >= once]
    first, last = with_mxu[0], with_mxu[-1]
    return {"bundles": len(use), "once": once, "before": first - once,
            "between": last - first + 1, "after": len(use) - 1 - last,
            "per_row": len(use) - once,
            "spill_stores": sum(1 for u in use if u[spill])}


def main(argv):
    if len(argv) > 2 and argv[1] == "--compile":
        return compile_one(argv[2])
    names = argv[1].split(",") if len(argv) > 1 else list(SHAPES)
    for name in names:
        with tempfile.TemporaryDirectory(prefix="paged_bundles_") as folder:
            env = dict(os.environ, JAX_PLATFORMS="cpu", LIBTPU_INIT_ARGS=(
                f"--xla_jf_dump_to={folder} --xla_jf_dump_llo_text=true"))
            run = subprocess.run([sys.executable, __file__, "--compile", name],
                                 env=env, capture_output=True, text=True)
            try:
                counts = read_dump(folder)
            except (SystemExit, IndexError, ValueError) as e:
                print(run.stderr[-3000:], file=sys.stderr)
                raise SystemExit(f"{name}: no dump to read ({e}); the compile's "
                                 f"exit code was {run.returncode}")
        print(json.dumps({"shape": name, **counts}), flush=True)


if __name__ == "__main__":
    main(sys.argv)
