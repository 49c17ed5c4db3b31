"""Readings that a cell's ``correct`` limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> [--fault F] SEED ...

Runs the cell once per seed in this one process (set-up is paid once per
seed, compiles once), each a whole run of ``harness.run_cell`` with a
window at the cell's own load, and also puts the configuration's control
(the reference one precision step below the configuration's) in the
program's place on the same sample, judged at the cell's limit.  With
``--fault`` (``faults.py``) the program's decode tick is broken as it is
built, to read that fault at the cell's size.  Prints one JSON line per
seed and, last, the lower reading (the largest gap of the program's
runs), the upper one (the smallest gap of the control), and whether
every program run came out correct and every control run did not.  Not
part of a benchmark run; PERF.md gives the readings and the limits set
from them.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=("token", "state"))
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU found", file=sys.stderr)
        return 1
    import harness
    if args.fault:
        import faults
        import system
        system.serve = faults.broken(system.serve, args.fault,
                                     cell.config["vocab_size"])
    lower, upper, verdicts, control_verdicts = [], [], [], []
    for seed in args.seeds:
        res = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=time.monotonic(), control=True)
        gap = res["checks"]["logit_gap"]["value"]
        lower.append(gap)
        if res["control_gap"] is not None:
            upper.append(res["control_gap"])
        verdicts.append(res["correct"])
        control_verdicts.append(res["control_correct"])
        print(json.dumps({"seed": seed, "gap": gap,
                          "control_gap": res["control_gap"],
                          "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "sample": res["sample"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "statuses": res["statuses"],
                          "metrics": {k: v["value"]
                                      for k, v in res["metrics"].items()},
                          "memory_bytes": res["device"]["memory_peak_bytes"],
                          "checks": res["checks"]}), flush=True)
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "seeds": len(args.seeds),
                      "lower_reading": max(lower),
                      "upper_reading": min(upper, default=None),
                      "program_correct_all": all(verdicts),
                      "control_correct_none": not any(control_verdicts)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
