"""Readings that the correctness limits are set from (not a benchmark run).

    for s in 1 2 3; do
        python3 bench/control.py --workload <cell> --seconds <s> --seed $s
    done

One run of the cell at its own size and load (``--seconds`` long enough to
compare as many served tokens as a run does), whose sample is scored twice:
the program's served tokens against the fp32 reference, and, at each of the
same positions, the token that the fp8 reference (the control, one
precision below the configurations' bf16) puts first.  Prints the seed's
numbers (the widest and the mean logit gap) on a line starting
``CONTROL``.  One seed a process: a second run in the same process can find
the chip's memory still held.  A limit lies above the largest program
reading and below the smallest control reading (PERF.md gives them).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from bench import run
    if jax.devices()[0].platform != "tpu" and not args.smoke:
        print("bench/control.py: JAX found no TPU", file=sys.stderr)
        return 2
    r = run.run_cell(args.workload, args.seed, args.seconds, False,
                     smoke=args.smoke, t_start=time.monotonic(), control=True)
    print("CONTROL " + json.dumps({
        "seed": args.seed, "metrics": r["metrics"],
        "program": {k: v["value"] for k, v in r["checks"].items()},
        "control": {k: v["value"] for k, v in r["control_checks"].items()}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
