#!/usr/bin/env python3
"""Find a fixed-rate cell's knee on the chip: run the cell at each rate of a
list, one process per rate, and report how late the load generator ran.

    python3 benchmarks/onchip/sweep.py --workload <name> --seed <n> \
        --seconds <s> --rates 1500,2000,2500

The knee is the highest rate at which the generator's queue does not grow
across the window: the window is cut into intervals (the traffic's
snapshot length where snapshots close in it, else one second), and a rate
is sustained where the generator catches up, sending some order within
2 ms of its due time, in at least 90% of them.  A stall, such as a refresh
or a collection, is then paid off before the next interval; past the knee
the queue never empties again.  The rule does not depend on how long a
stall is, so a refresh that slows as its community grows does not move
the knee by itself.  The cell then runs at 4/5 of the knee, written into
its traffic file as a number.  This parent never imports JAX, so each
child has the chip to itself.
"""
import argparse
import json
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def run_rate(workload: str, seed: int, seconds: float, rate: float) -> dict:
    cmd = [sys.executable, str(HERE / "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", "0",
           "--rate", str(rate)]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if proc.returncode != 0 or not lines:
        sys.stderr.write(proc.stderr[-4000:])
        raise SystemExit(f"sweep: rate {rate} failed (exit {proc.returncode})")
    return json.loads(lines[-1])


CAUGHT_UP = 0.9


def sustained(diag: dict) -> bool:
    return diag["caught_up_share"] >= CAUGHT_UP


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        res = run_rate(args.workload, args.seed + i, args.seconds, rate)
        d = res["diag"]
        ok = sustained(d)
        knee = rate if ok and (knee is None or rate > knee) else knee
        print(json.dumps({
            "rate_per_s": rate, "sustained": ok, "correct": res["correct"],
            "caught_up_share": d["caught_up_share"],
            "longest_behind": d["longest_behind"],
            "late_p95_first_ms": d["gen_late_p95_ms_first_half"],
            "late_p95_second_ms": d["gen_late_p95_ms_second_half"],
            "late_max_ms": d["gen_late_max_ms"],
            "score_p50_ms": d["end_to_end"].get("score_p50_ms"),
            "score_p95_ms": d["end_to_end"].get("score_p95_ms"),
            "staleness_ms": d["end_to_end"].get("staleness_ms"),
            "orders_per_flush": d["stats"]["scored"] / max(d["stats"]["flushes"], 1),
            "setup_s": d["end_to_end"]["setup_s"],
            "checks": {k: v["value"] for k, v in res["checks"].items()},
            "weight_gap": d.get("weight_gap")}), flush=True)
    print(json.dumps({"knee_per_s": knee,
                      "rate_per_s": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
