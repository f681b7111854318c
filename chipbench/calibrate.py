"""Readings for the limits of ``correct``, on the chip, many seeds in one process:
``python3 -m chipbench.calibrate --workload <cell> --seeds 1,2,3 [--control 3] [--seconds s]``.
For each seed it prints every number that a run of the cell reads against the plain
reference (the lower readings, those held to a limit and those not); for the first
``--control`` seeds also what the control reads (the reference in the next lower
precision, put in the program's place) and, for a training cell, the planted faults.
Like a run, it gives nothing where it finds no chip."""
import argparse
import json
import time

from chipbench import harness


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--only", default="control_fp8,fault_half_batch")
    a = ap.parse_args()
    bench, cell, cfg, mix = harness.cell_of(a.workload)
    harness.chip(cell)
    driver = harness.module("drivers", cfg["driver"])
    ref = harness.module("reference", cfg["reference"])
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t = time.perf_counter()
        ctx = {"cell": cell, "cfg": cfg, "mix": mix, "seed": seed, "trace": False,
               "seconds": a.seconds or bench["run_seconds"], "chips": cell["chips"],
               "t_start": t, "build": None, "keep": n < a.control, "only": a.only}
        res = driver.run(ctx)
        line = {"seed": seed, "program": res["numbers"], "end_to_end": res["end_to_end"],
                "attempted": res["attempted"], "failed": res["failed"]}
        if n < a.control:
            line.update(driver.controls(ctx, ref, res))
        line["seconds"] = round(time.perf_counter() - t, 1)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
