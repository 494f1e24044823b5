"""Benchmark harness — one section per paper table/figure + framework extras.

  table3    paper Table 3 (MLP / LGB / LNN-GAT / LNN-GCN, ROC-AUC + AP)
  latency   paper claim 3 (lambda 1-hop KV inference vs monolithic GNN)
  streaming serving-engine replay (throughput, p50/p95/p99, staleness curve)
  multiworker sharded speed-layer sweep (latency vs N, queue depth, steals)
  stage2    fused vs unfused speed-layer scoring per micro-batch bucket
  kernels   Pallas-kernel micro-bench (XLA ref timing + v5e roofline projection)
  roofline  aggregated dry-run roofline table (if dry-run records exist)

  gateway   HTTP gateway under open-loop Poisson load (429/503/canary gates)
  recovery  crash recovery (checkpoint write/restore latency, replay-suffix
            cost vs log length, bit-identical recovery gate)
  learning  continuous-learning loop on a drifting attack stream (recall
            recovery + shadow-gated promotion + auto-rollback gates)
  procpool  process-backed worker pool (inline-vs-process replay parity
            gate + N=4 vs N=1 throughput-scaling gate)

``--smoke`` runs only the serving benches (streaming + multiworker + stage2
+ gateway + recovery + learning + procpool) at tiny sizes — seconds, not minutes — then validates the emitted
``BENCH_*.json`` records against their schemas (``tools/check_bench_schema``).
That is the CI ``bench-smoke`` gate: it fails on crash or schema drift.

Prints ``name,us_per_call,derived`` CSV at the end for machine consumption.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _streaming_rows(csv_rows, stream) -> None:
    for bs, t in stream["throughput"].items():
        csv_rows.append((f"streaming/throughput_{bs}", f"{t['us_per_event']:.1f}",
                         f"{t['events_per_s']:.0f}eps"))
    csv_rows.append(("streaming/microbatch_speedup", "",
                     f"{stream['microbatch_speedup']:.1f}x"))
    for load, pct in stream["latency"].items():
        csv_rows.append((f"streaming/{load}/p99", f"{pct['p99']*1e3:.0f}",
                         f"p50={pct['p50']:.2f}ms,p99={pct['p99']:.2f}ms"))
    for p in stream["multiworker"]["sweep"]:
        pct = p["latency_ms"]
        csv_rows.append((
            f"multiworker/n{p['num_workers']}/p99", f"{pct['p99']*1e3:.0f}",
            f"p50={pct['p50']:.2f}ms,p99={pct['p99']:.2f}ms,"
            f"steal_rate={p['steal_rate']:.3f}",
        ))
    par = stream["multiworker"]["parity"]
    csv_rows.append(("multiworker/parity", "",
                     f"bit_identical={par['bit_identical']}"))
    pb = stream["refresh_put_batch"]
    csv_rows.append(("streaming/put_batch_speedup",
                     f"{pb['put_batch_s']*1e6/max(1, pb['n']):.2f}",
                     f"{pb['speedup']:.1f}x"))
    rf = stream["refresh_scope"]
    csv_rows.append(("refresh/community_local", "",
                     f"nodes_speedup_final={rf['nodes_speedup_final']:.1f}x,"
                     f"sublinear={rf['sublinear']},"
                     f"parity={rf['parity']['bit_identical']}"))


def _hetero_rows(csv_rows, ht) -> None:
    for model, a in ht["auc"].items():
        budgets = ht["recall"][model]
        top = sorted(budgets)[0]
        csv_rows.append((
            f"hetero/{model}/auc", "",
            f"auc={a:.3f},ring@{top}={budgets[top]['ring']:.2f}",
        ))
    csv_rows.append(("hetero/gates", "",
                     ",".join(f"{k}={v}" for k, v in ht["gates"].items())))


def _stage2_rows(csv_rows, s2) -> None:
    for bs, r in s2["per_batch"].items():
        csv_rows.append((f"stage2/fused_b{bs}", f"{r['fused_us']:.1f}",
                         f"speedup={r['speedup']:.2f}x"))


def _recovery_rows(csv_rows, rec) -> None:
    ck, rs = rec["checkpoint"], rec["restore"]
    csv_rows.append(("recovery/checkpoint_write", f"{ck['write_s']*1e6:.0f}",
                     f"size={ck['size_bytes']}B"))
    csv_rows.append((
        "recovery/restore", f"{rs['with_checkpoint_s']*1e6:.0f}",
        f"replayed={rs['replayed_with_checkpoint']},"
        f"genesis_replayed={rs['replayed_genesis']},"
        f"bit_identical={rec['gates']['recovery_bit_identical']}",
    ))


def _learning_rows(csv_rows, lrn) -> None:
    csv_rows.append((
        "learning/recall_recovery", "",
        f"frozen={lrn['frozen_ring_recall']:.3f},"
        f"recovered={lrn['recovered_ring_recall']:.3f},"
        f"promotions={len(lrn['promotions'])},"
        f"rolled_back={lrn['regression']['rolled_back']}",
    ))
    csv_rows.append(("learning/gates", "",
                     ",".join(f"{k}={v}" for k, v in lrn["gates"].items())))


def _procpool_rows(csv_rows, pp) -> None:
    sc = pp["scaling"]
    for p in sc["sweep"]:
        csv_rows.append((
            f"procpool/n{p['num_workers']}",
            f"{p['wall_s']*1e6/max(1, pp['n_events']):.0f}",
            f"{p['events_per_s']:.0f}eps",
        ))
    csv_rows.append((
        "procpool/scaling", "",
        f"speedup_4v1={sc['speedup_4v1']:.2f}x,cores={sc['cores']},"
        f"limited_by_cores={sc['limited_by_cores']}",
    ))
    csv_rows.append(("procpool/gates", "",
                     ",".join(f"{k}={v}" for k, v in pp["gates"].items())))


def _gateway_rows(csv_rows, gwr) -> None:
    for name, s in gwr["scenarios"].items():
        pct = s["latency_ms"]
        csv_rows.append((
            f"gateway/{name}/p99", f"{pct['p99']*1e3:.0f}",
            f"p50={pct['p50']:.2f}ms,p99={pct['p99']:.2f}ms,"
            f"429={s['rejected_429']},503={s['rejected_503']}",
        ))
    csv_rows.append(("gateway/gates", "",
                     ",".join(f"{k}={v}" for k, v in gwr["gates"].items())))


def run_smoke() -> None:
    """The CI bench-smoke gate: serving benches at tiny sizes + schema check."""
    csv_rows = [("name", "us_per_call", "derived")]
    os.makedirs("experiments", exist_ok=True)

    # smoke records land under experiments/smoke/ (never clobbering the
    # curated full-run records); validate exactly what this run wrote
    from benchmarks.streaming_bench import main as streaming_main
    stream = streaming_main(smoke=True)   # writes BENCH_streaming + _multiworker
    _streaming_rows(csv_rows, stream)
    _hetero_rows(csv_rows, stream["hetero"])  # writes BENCH_hetero.json

    from benchmarks.stage2_bench import main as stage2_main
    s2 = stage2_main(smoke=True)          # writes BENCH_stage2.json
    _stage2_rows(csv_rows, s2)

    from benchmarks.gateway_bench import main as gateway_main
    gwr = gateway_main(smoke=True)        # writes BENCH_gateway.json
    _gateway_rows(csv_rows, gwr)

    from benchmarks.recovery_bench import main as recovery_main
    rec = recovery_main(smoke=True)       # writes BENCH_recovery.json
    _recovery_rows(csv_rows, rec)

    from benchmarks.learning_bench import main as learning_main
    lrn = learning_main(smoke=True)       # writes BENCH_learning.json
    _learning_rows(csv_rows, lrn)

    from benchmarks.procpool_bench import main as procpool_main
    pp = procpool_main(smoke=True)        # writes BENCH_procpool.json
    _procpool_rows(csv_rows, pp)

    from tools.check_bench_schema import main as schema_main
    rc = schema_main([os.path.join("experiments", "smoke", name) for name in
                      ("BENCH_streaming.json", "BENCH_stage2.json",
                       "BENCH_multiworker.json", "BENCH_refresh.json",
                       "BENCH_gateway.json", "BENCH_recovery.json",
                       "BENCH_hetero.json", "BENCH_learning.json",
                       "BENCH_procpool.json")])
    if rc != 0:
        raise SystemExit(rc)

    print("\n# CSV")
    for row in csv_rows:
        print(",".join(str(c) for c in row))


def run_full() -> None:
    csv_rows = [("name", "us_per_call", "derived")]
    os.makedirs("experiments", exist_ok=True)

    from benchmarks.table3 import main as table3_main
    seeds = (0, 1, 2) if os.environ.get("BENCH_FULL") else (0, 1)
    table = table3_main(seeds=seeds)
    json.dump(table, open("experiments/table3.json", "w"), indent=1)
    for name, r in table.items():
        csv_rows.append((f"table3/{name.replace(' ', '')}/auc",
                         f"{r['train_seconds']*1e6:.0f}", f"{r['roc_auc_mean']:.4f}"))
        csv_rows.append((f"table3/{name.replace(' ', '')}/ap",
                         f"{r['train_seconds']*1e6:.0f}", f"{r['ap_mean']:.4f}"))

    from benchmarks.latency import main as latency_main
    lat = latency_main()
    json.dump(lat, open("experiments/latency.json", "w"), indent=1)
    csv_rows.append(("latency/lambda_single", f"{lat['lambda_ms_per_request']*1e3:.1f}",
                     f"speedup={lat['speedup_single']:.1f}x"))
    csv_rows.append(("latency/lambda_batched", f"{lat['lambda_batched_ms_per_request']*1e3:.1f}",
                     f"speedup={lat['speedup_batched']:.1f}x"))
    csv_rows.append(("latency/monolithic", f"{lat['monolithic_ms_per_request']*1e3:.1f}", ""))

    from benchmarks.streaming_bench import main as streaming_main
    stream = streaming_main()   # writes BENCH_streaming + BENCH_multiworker
    _streaming_rows(csv_rows, stream)
    _hetero_rows(csv_rows, stream["hetero"])  # writes BENCH_hetero.json

    from benchmarks.stage2_bench import main as stage2_main
    s2 = stage2_main()   # writes experiments/BENCH_stage2.json
    _stage2_rows(csv_rows, s2)

    from benchmarks.gateway_bench import main as gateway_main
    gwr = gateway_main()   # writes experiments/BENCH_gateway.json
    _gateway_rows(csv_rows, gwr)

    from benchmarks.recovery_bench import main as recovery_main
    rec = recovery_main()   # writes experiments/BENCH_recovery.json
    _recovery_rows(csv_rows, rec)

    from benchmarks.learning_bench import main as learning_main
    lrn = learning_main()   # writes experiments/BENCH_learning.json
    _learning_rows(csv_rows, lrn)

    from benchmarks.procpool_bench import main as procpool_main
    pp = procpool_main()   # writes experiments/BENCH_procpool.json
    _procpool_rows(csv_rows, pp)

    from benchmarks.kernels_bench import main as kernels_main
    ker = kernels_main()
    json.dump(ker, open("experiments/kernels.json", "w"), indent=1)
    for r in ker:
        csv_rows.append((f"kernel/{r['name']}", f"{r['us_per_call_cpu_xla']:.1f}",
                         f"v5e_roofline_us={r['v5e_roofline_us']:.2f}"))

    from benchmarks.roofline_table import load_records
    recs = load_records("single")
    ok = [r for r in recs if r.get("status") == "ok"]
    if ok:
        print(f"\n# Roofline: {len(ok)} dry-run records (see EXPERIMENTS.md §Roofline)")
        for r in ok[:5]:
            csv_rows.append((f"roofline/{r['arch']}/{r['shape']}",
                             f"{max(r['t_compute'], r['t_memory'], r['t_collective'])*1e6:.0f}",
                             r["bottleneck"]))

    print("\n# CSV")
    for row in csv_rows:
        print(",".join(str(c) for c in row))


def main() -> None:
    from repro.utils.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="serving benches only, tiny sizes, schema-checked "
                         "(the CI bench-smoke gate)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        run_smoke()
    else:
        run_full()


if __name__ == '__main__':
    main()
