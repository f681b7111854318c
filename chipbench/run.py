"""``python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``:
one cell, once, in this process. The last line of standard output is the result."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from chipbench import harness
    out = harness.measure(a.workload, a.seed, a.seconds, a.trace, t_start=T_START)
    sys.stdout.flush()
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
