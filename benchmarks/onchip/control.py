#!/usr/bin/env python3
"""The control of ``correct``: the plain reference at the next precision
below the configuration's (float32 at three bfloat16 passes, ``high``, for
float32 at ``highest``), put in the program's place at a cell's own size.

    python3 benchmarks/onchip/control.py --workload <name> --seeds 1,2,3 \
        [--orders <n>]

For each seed it builds the cell's stream, answers every window order (the
first ``--orders`` of a backlog) with the lower-precision reference, and
prints the compared numbers beside the limits of the cell's configuration,
one JSON line per seed.  The control has to come out as not correct: the
command exits 1 where it comes out correct on any seed.  It runs on the
host alone.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cell  # noqa: E402
import correctness  # noqa: E402
import traffic_gen  # noqa: E402


def control(workload: dict, seed: int, seconds: float, orders=None) -> dict:
    config = cell.find_config(workload["config"])
    traffic = traffic_gen.load_traffic(workload["traffic"])
    stream = traffic_gen.generate(traffic, config["service"]["model"]["feat_dim"],
                                  seed, seconds)
    n = min(orders or len(stream.window), len(stream.window))
    ref = correctness.load_reference(config)
    closed = cell.closed_in_window(stream, n)
    args = (ref, config, seed, cell.reference_orders(stream, n),
            len(stream.history) + len(stream.prime), closed)
    exp = correctness.Expected(*args)
    low = correctness.Expected(*args, precision="high")
    numbers = correctness.compare(correctness.control_observed(low, n), exp,
                                  closes=bool(closed))
    ok, checks = correctness.judge(numbers, config["limits"])
    return {"workload": workload["name"], "seed": seed, "orders": n,
            "correct": ok, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--orders", type=int, default=None)
    args = ap.parse_args(argv)
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = control(wl, seed, bench["run_seconds"], args.orders)
        print(json.dumps(res), flush=True)
        if res["correct"]:
            passed.append(seed)
    if passed:
        print(f"control: correct on seeds {passed}; the limits do not "
              "separate it", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
