"""The benchmark's HGT parts (``lnn-hgt``), on the CPU at tiny sizes: the
work counts, the shares they give, the control of ``correct`` and one
sound run.  Pallas kernels run in interpret mode."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import cell  # noqa: E402
import correctness  # noqa: E402
import layer_spans  # noqa: E402
import traffic_gen  # noqa: E402
import work_counts  # noqa: E402
import work_counts_hgt  # noqa: E402
from test_onchip_bench import PEAKS, _small_orders, run_tiny  # noqa: E402

CONFIG = cell.find_config("lnn-hgt")
MODEL = dict(CONFIG["service"]["model"],
             max_history=CONFIG["service"]["engine"]["max_history"])


def test_hgt_stage1_count_is_the_same_for_the_kernels_and_a_gather():
    """The nodes and edges the harness reads off a padded refresh graph
    (what the kernels ran) are the plain reference's, so the counts agree."""
    from repro.core.dds import IncrementalDDSBuilder
    from repro.core.graph import pad_graph

    ref = correctness.load_reference(CONFIG)
    orders = _small_orders()
    b = IncrementalDDSBuilder(48, max_history=8)
    for i in range(len(orders)):
        b.add_order(orders.entities[i].tolist(), int(orders.snapshot[i]),
                    orders.features[i])
    dds = b.build()
    pg = pad_graph(dds.coo, num_nodes=traffic_gen.pow2_bin(dds.coo.num_nodes),
                   max_deg=32)
    kernel_graph = layer_spans._graph_size(pg)
    g = ref.Stage1Graph(orders.snapshot, orders.entities, orders.features,
                        max_history=8, max_deg=32)
    assert kernel_graph == (g.num_nodes, len(g.src))
    assert work_counts_hgt.stage1_graph(MODEL, *kernel_graph) == \
        work_counts_hgt.stage1_graph(MODEL, g.num_nodes, len(g.src))


def _kernels_stage1(model, n, d):
    """(operations, bytes) the stage-1 kernels do on a padded bin of ``n``
    nodes and ``d`` slots: per layer the K and M projections of three
    relations with W^ATT / W^MSG folded in, Q and A for each of three
    types, each of the ``n * d`` slots' logit and message, the
    sinusoid's table; the gathered rows read from HBM."""
    H, F, L = model["hidden_dim"], model["feat_dim"], model["num_gnn_layers"]
    T = 64
    per_layer = (2 * 3 + 3 + 3) * n * H * H + 2 * n * d * H + 7 * T * H * H
    ops = 2.0 * (n * F * H + (L - 1) * per_layer)
    byts = 4.0 * (n * F + (L - 1) * (6 * n * H + 2 * n * d * H + 3 * n * d))
    return ops, byts


def test_every_hgt_share_stays_under_100_percent_at_the_tested_shapes():
    peak = PEAKS["TPU v5 lite"]
    for budget in (64, 4096, 32768):
        nodes, edges = budget // 2 + 1, (budget // 2 + 1) * 12
        alg = work_counts_hgt.stage1_graph(MODEL, nodes, edges)
        impl = _kernels_stage1(MODEL, budget, 32)
        assert alg[0] <= impl[0] and alg[1] <= impl[1]
        assert work_counts.roofline_s(*alg, peak) <= \
            work_counts.roofline_s(*impl, peak)
    # stage 2's least bytes: no more weights than the hgt body is handed
    import jax

    from repro.core import LNNConfig, lnn_init
    from repro.kernels.stage2_score import flatten_hgt_stage2_params

    cfg = LNNConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                       for k, v in CONFIG["service"]["model"].items()})
    flat = jax.eval_shape(lambda k: flatten_hgt_stage2_params(
        lnn_init(k, cfg)), jax.random.PRNGKey(0))
    handed = sum(int(np.prod(a.shape)) for a in flat)
    assert work_counts_hgt.stage2_params(MODEL) <= handed
    for n in (1, 2, 9, 16):
        ops, byts = work_counts_hgt.stage2_flush(MODEL, n, 7)
        bucket = 16 if n > 8 else max(2, 1 << (n - 1).bit_length())
        ops_b, byts_b = work_counts_hgt.stage2_flush(MODEL, bucket, 8)
        assert work_counts.roofline_s(ops, byts, peak) <= \
            work_counts.roofline_s(ops_b, byts_b, peak)


def test_hgt_control_at_the_lower_precision_is_not_correct():
    """The HGT reference at three bf16 passes, put in the program's place,
    fails lnn-hgt's limits; at float32 it passes them."""
    ref = correctness.load_reference(CONFIG)
    orders = _small_orders(seed=8)
    n_hist = len(orders) - 40
    args = (ref, CONFIG, 8, orders, n_hist, [])
    exp = correctness.Expected(*args)
    same = correctness.control_observed(exp, 40)
    ok, _ = correctness.judge(correctness.compare(same, exp, False),
                              CONFIG["limits"])
    assert ok
    low = correctness.Expected(*args, precision="high")
    ok, checks = correctness.judge(
        correctness.compare(correctness.control_observed(low, 40), exp, False),
        CONFIG["limits"])
    assert not ok, checks


def test_a_sound_tiny_hgt_run_is_correct():
    r = run_tiny("lnn-hgt.refresh-shared-ip", trace=True, rate=30.0)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["diag"]["closes"] >= 1
    assert r["diag"]["weight_gap"] == 0.0
    assert {"refresh_ms_per_close.hgt", "mfu.hgt"} <= set(r["metrics"])


@pytest.mark.parametrize("name", ["stage1_roofline.hgt", "stage2_roofline.hgt",
                                  "mfu.hgt"])
def test_hgt_readers_stay_silent_on_another_model(name):
    """Run on a parent whose configuration is not HGT's, a reader returns
    nothing rather than counting the wrong algorithm."""
    from types import SimpleNamespace

    ctx = SimpleNamespace(
        config=cell.find_config("lnn-gat"), peak=PEAKS["TPU v5 lite"],
        stage1_graphs=[(100, 1000)], flush_sizes=[4], slots_per_order=7.0,
        trace={"program_s": {"stage1": 1.0}, "kernel_s": {"stage2_score": 1.0},
               "spans": {"refresher.on_windows_closed": {"total_s": 1.0,
                                                         "count": 1}}})
    assert cell.find_metric_reader(name)(ctx, {"name": name}) is None
