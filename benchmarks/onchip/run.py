#!/usr/bin/env python3
"""The on-chip benchmark of the LNN fraud scorer: one cell per process.

    python3 benchmarks/onchip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Runs from the root of a checkout.  The cell's configuration, traffic and
per-layer metrics are named in ``BENCHMARK.json`` and found as files under
this directory.  It refuses any platform but a TPU, and fewer chips than
the cell asks for, before any set-up.  The last line of standard output is
the result as one JSON object; the compared numbers, each beside its
limit, are the last lines of standard error.

``--rate`` overrides the traffic file's rate: the knee sweep
(``sweep.py``) uses it, the benchmark's own runs never do.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the traffic's rate (knee sweep only)")
    return ap.parse_args(argv)


def require_chip(chips: int) -> None:
    """Exit non-zero unless JAX's devices are TPUs, at least ``chips``, of
    a kind the table of peaks knows."""
    import jax

    devs = [d for d in jax.devices() if d.platform == "tpu"]
    if jax.devices()[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"run.py: needs {chips} TPU chip(s); JAX sees "
            f"{[d.platform for d in jax.devices()]}")
    with open(HERE / "peaks.json") as f:
        if devs[0].device_kind not in json.load(f):
            raise SystemExit(f"run.py: no peaks for {devs[0].device_kind!r}")


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    args = parse(argv)
    bench_path = ROOT / "BENCHMARK.json"
    with open(bench_path) as f:
        bench = json.load(f)
    workload = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
    if workload is None:
        raise SystemExit(f"run.py: no workload {args.workload!r}")
    require_chip(int(workload["chips"]))

    import jax

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import cell
    import traffic_gen

    config = cell.find_config(workload["config"])
    traffic = traffic_gen.load_traffic(workload["traffic"])
    result = cell.run_cell(bench, workload, config, traffic, args.seed,
                           args.seconds, bool(args.trace), T_START,
                           rate_per_s=args.rate)
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
