"""Tests of the on-chip benchmark's own parts, on the CPU at tiny sizes.

The chip runs nothing here: cells run through ``cell.run_cell`` (which
skips ``run.py``'s look for a chip) with the Pallas kernels interpreted.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import cell  # noqa: E402
import correctness  # noqa: E402
import layer_spans  # noqa: E402
import trace_metrics  # noqa: E402
import traffic_gen  # noqa: E402
import work_counts  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((HERE / "peaks.json").read_text())


def workload(name):
    return next(w for w in BENCH["workloads"] if w["name"] == name)


def tiny(name, rate=60.0, snapshot_s=0.5):
    """A cell's configuration and traffic cut to a size the interpreter
    runs in seconds."""
    wl = workload(name)
    config = cell.find_config(wl["config"])
    config["service"]["refresh"]["community_size"] = 256
    traffic = copy.deepcopy(traffic_gen.load_traffic(wl["traffic"]))
    pop = traffic["population"]
    pop["users"] = 40
    pop["shared_ip_groups"] = [{"groups": 2, "users": 4}]
    if "nat_pool" in pop:
        pop["nat_pool"] = {"users": 10, "ips": 3}
    traffic["history"] = {"days": 2, "orders_per_user": 1}
    if traffic["window"]["arrivals"] == "backlog":
        traffic["window"]["orders"] = 400
    else:
        traffic["window"]["rate_per_s"] = rate
    if "snapshot_s" in traffic["window"]:
        traffic["window"]["snapshot_s"] = snapshot_s
    return wl, config, traffic


def run_tiny(name, seconds=1.0, trace=False, seed=5, **kw):
    wl, config, traffic = tiny(name, **kw)
    peaks = dict(PEAKS, cpu=PEAKS["TPU v5 lite"])
    return cell.run_cell(BENCH, wl, config, traffic, seed, seconds, trace,
                         time.perf_counter(), peaks=peaks)


# ----------------------------------------------------------------- traffic
def test_generator_gives_the_same_events_for_a_seed():
    params = traffic_gen.load_traffic("serve-poisson")
    a = traffic_gen.generate(params, 48, 2 ** 31 + 11, 2.0)
    b = traffic_gen.generate(params, 48, 2 ** 31 + 11, 2.0)
    c = traffic_gen.generate(params, 48, 3, 2.0)
    for part in ("history", "prime", "window"):
        for f in ("snapshot", "entities", "features", "order_id", "due"):
            np.testing.assert_array_equal(getattr(getattr(a, part), f),
                                          getattr(getattr(b, part), f))
    assert not np.array_equal(a.window.features, c.window.features)
    # the seed reorders the work, it does not change it
    np.testing.assert_allclose(np.sort(np.diff(a.window.due)),
                               np.sort(np.diff(c.window.due)), atol=1e-6)
    assert len(a.window) == len(c.window) == \
        round(params["window"]["rate_per_s"] * 2.0)


def test_refresh_traffic_giant_bin_is_the_same_across_seeds():
    params = traffic_gen.load_traffic("refresh-shared-ip")
    cs = cell.find_config("lnn-gat")["service"]["refresh"]["community_size"]
    bins = set()
    for seed in (1, 977, 2 ** 31 + 5):
        s = traffic_gen.generate(params, 48, seed, BENCH["run_seconds"])
        assert len(s.window_closes()) >= 20
        for parts in ([s.history, s.prime], [s.history, s.prime, s.window]):
            nodes = traffic_gen.community_nodes(traffic_gen.Orders.concat(parts))
            giant = max(nodes.values())
            assert giant > cs
            bins.add(traffic_gen.pow2_bin(giant))
    assert bins == {32768}


def test_a_traffic_file_added_under_traffic_is_found_by_name(tmp_path):
    for sub in ("traffic", "configs", "metrics"):
        shutil.copytree(HERE / sub, tmp_path / sub)
    new = traffic_gen.load_traffic("serve-poisson")
    new["window"]["rate_per_s"] = 123.0
    (tmp_path / "traffic" / "added-mix.json").write_text(json.dumps(new))
    found = traffic_gen.load_traffic("added-mix", root=tmp_path)
    assert found["window"]["rate_per_s"] == 123.0
    s = traffic_gen.generate(found, 48, 1, 2.0)
    assert len(s.window) == round(123.0 * 2.0)
    (tmp_path / "metrics" / "added_metric.py").write_text(
        "def read(ctx, metric):\n    return 7.0\n")
    assert cell.find_metric_reader("added_metric.lat", tmp_path)(None, {}) == 7.0
    assert cell.find_metric_reader("no_such_metric", tmp_path) is None
    with pytest.raises(FileNotFoundError):
        traffic_gen.find_traffic("no-such-mix", root=tmp_path)


def test_harness_exits_non_zero_on_a_non_tpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "lnn-gcn.serve-poisson", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


# --------------------------------------------------------------- the window
class FakeService:
    """Answers every order at once, except that submitting order ``stall_at``
    takes ``stall_s``; holds back the last ``hold`` orders until drain."""

    def __init__(self, stall_at=-1, stall_s=0.0, hold=0, n=0):
        self.stall_at, self.stall_s = stall_at, stall_s
        self.hold_from = n - hold if hold else float("inf")
        self.i, self.held, self.scored = 0, [], 0

    def _resp(self, ev):
        return SimpleNamespace(request=SimpleNamespace(tag=ev), batch_size=1)

    def submit(self, ev):
        if self.i == self.stall_at:
            time.sleep(self.stall_s)
        self.i += 1
        if self.i > self.hold_from:
            self.held.append(ev)
            return []
        self.scored += 1
        return [self._resp(ev)]

    def drain(self):
        out, self.held = [self._resp(e) for e in self.held], []
        self.scored += len(out)
        return out

    def stats(self):
        return SimpleNamespace(scored=self.scored, flushes=self.scored,
                               refreshes=0, requests=self.i)


class NullProbe:
    recording = False

    def window(self):
        from contextlib import nullcontext
        return nullcontext()

    wait = window


def stream_of(due):
    n = len(due)
    orders = traffic_gen.Orders(
        snapshot=np.zeros(n, np.int64), entities=np.zeros((n, 7), np.int64),
        features=np.zeros((n, 4), np.float32), order_id=np.arange(n),
        due=np.asarray(due, np.float64))
    return SimpleNamespace(window=orders)


def test_an_injected_stall_raises_the_due_time_latency_behind_it():
    due = np.arange(40) * 0.002                 # one order every 2 ms
    stream = stream_of(due)
    events = [SimpleNamespace(order_id=i) for i in range(40)]
    win = cell.run_window(FakeService(stall_at=10, stall_s=0.05), NullProbe(),
                          stream, events, 1.0, backlog=False)
    lat = win.answered_at - (win.t0 + win.due)
    # the stalled order and every order due during its 50 ms stall wait for
    # it; timed from their send instead, they would read about zero
    assert lat[10] >= 0.045
    assert all(lat[i] >= 0.05 - (due[i] - due[10]) - 0.004
               for i in range(11, 35))
    assert np.median(lat[:10]) < 0.004
    assert win.late[11] >= 0.04


def test_orders_per_s_counts_only_orders_scored_in_the_window():
    n = 200
    stream = stream_of(np.zeros(n))
    events = [SimpleNamespace(order_id=i) for i in range(n)]
    svc = FakeService(hold=50, n=n)
    win = cell.run_window(svc, NullProbe(), stream, events, 0.2, backlog=True)
    assert win.submitted == n
    e2e = cell.end_to_end(win, SimpleNamespace(closes=[]),
                          SimpleNamespace(window_closes=lambda: []), 1.0)
    scored_in_window = n - 50
    assert e2e["orders_per_s"] == pytest.approx(
        scored_in_window / (win.t_stop - win.t0))


def test_the_longest_stretch_behind_names_the_stall_and_its_cause():
    due = np.arange(60) * 0.002
    stream = stream_of(due)
    events = [SimpleNamespace(order_id=i) for i in range(60)]
    win = cell.run_window(FakeService(stall_at=20, stall_s=0.06), NullProbe(),
                          stream, events, 1.0, backlog=False)
    gc_span = (0, win.sent_at[20] + 0.01, win.sent_at[20] + 0.05)
    b = cell.longest_behind(win, [gc_span], [])
    # the sleep holds the thread off the CPU; the planted collection
    # overlaps 40 ms of it
    assert b["s"] >= 0.055 and b["late_max_ms"] >= 50
    assert b["cpu_s"] < 0.5 * b["s"]
    assert b["gc_s"] == pytest.approx(0.04, abs=1e-6)
    assert 0.035 <= b["at_s"] <= 0.05


@pytest.mark.parametrize("grows", [False, True])
def test_the_knee_rule_reads_whether_the_queue_empties(grows):
    import sweep

    due = np.arange(3000) * 1e-3                      # 3 s at 1000/s
    # a 100 ms stall at the start of every second, paid off within it; or
    # lateness that grows all through the window
    late = np.where(grows, due * 0.2, np.maximum(0.1 - (due % 1.0), 0.0))
    win = SimpleNamespace(due=due, late=late)
    share = cell.caught_up_share(win, 1.0)
    assert sweep.sustained({"caught_up_share": share}) is (not grows)
    assert share == pytest.approx(1 / 3 if grows else 1.0)


def test_control_command_exits_non_zero_where_the_control_is_correct(
        monkeypatch):
    import control

    verdicts = iter([False, True])
    monkeypatch.setattr(control, "control", lambda wl, seed, s, n: {
        "seed": seed, "correct": next(verdicts)})
    assert control.main(["--workload", "lnn-gcn.serve-poisson",
                         "--seeds", "1,2"]) == 1
    monkeypatch.setattr(control, "control", lambda wl, seed, s, n: {
        "seed": seed, "correct": False})
    assert control.main(["--workload", "lnn-gcn.serve-poisson",
                         "--seeds", "1,2"]) == 0


# ------------------------------------------------------------ trace reduce
def test_trace_reduction_on_a_hand_built_trace():
    ms = 1_000_000
    tr = trace_metrics.Trace(
        host=sorted([
            ("bench.window", 0, 100 * ms),
            ("engine.ingest", 10 * ms, 30 * ms),
            ("refresher.on_windows_closed", 15 * ms, 25 * ms),
            ("stage2", 40 * ms, 50 * ms),
            ("gen.wait", 60 * ms, 90 * ms),
        ], key=lambda s: (s[1], -s[2])),
        ops=[("_stage2_score_pallas.1", 42 * ms, 44 * ms),
             ("_edge_softmax_agg_pallas", 16 * ms, 20 * ms),
             ("_fusion.3", 19 * ms, 22 * ms)],
        modules=[("jit__lambda", 16 * ms, 22 * ms),
                 ("jit__lambda", 41 * ms, 45 * ms)],
        devices=1)
    r = trace_metrics.reduce(tr)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.008)          # 16-22 and 42-44
    assert r["kernel_s"]["stage2_score_pallas"] == pytest.approx(0.002)
    assert r["program_s"] == pytest.approx({"stage2": 0.004, "stage1": 0.006})
    idle = dict(r["idle_gaps"])
    assert idle["gen.wait"] == pytest.approx(0.030)
    assert idle["engine.ingest"] == pytest.approx(0.010)          # 10-15, 25-30
    assert idle["refresher.on_windows_closed"] == pytest.approx(0.004)
    assert idle["stage2"] == pytest.approx(0.008)
    assert idle[trace_metrics.OTHER] == pytest.approx(0.040)
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(0.1)
    spans = r["spans"]
    assert spans["engine.ingest"]["self_s"] == pytest.approx(0.010)
    assert spans["engine.ingest"]["total_s"] == pytest.approx(0.020)


def test_only_device_planes_that_run_ops_count_as_chips():
    """A v5e profile holds ``/device:TPU:0`` and a
    ``/device:CUSTOM:Megascale Trace`` plane; busy time is per chip."""
    ms = 1_000_000

    def ev(name, s, e):
        return SimpleNamespace(name=name, start_ns=s, duration_ns=e - s)

    def plane(name, lines):
        return SimpleNamespace(name=name, lines=[
            SimpleNamespace(name=n, events=evs) for n, evs in lines])

    planes = [
        plane("/device:TPU:0", [("XLA Ops", [ev("%stage2_score_pallas.1 = f32[8]",
                                                2 * ms, 6 * ms)]),
                                ("XLA Modules", [ev("jit__lambda", 1 * ms, 7 * ms)])]),
        plane("/device:CUSTOM:Megascale Trace", []),
        plane("/host:CPU", [("python3", [ev("bench.window", 0, 10 * ms),
                                         ev("stage2", 1 * ms, 8 * ms),
                                         ev("unrelated", 0, 1 * ms)])]),
    ]
    tr = trace_metrics.from_planes(planes, layer_spans.SPAN_NAMES
                                   + (trace_metrics.WINDOW_SPAN,))
    assert tr.devices == 1 and [h[0] for h in tr.host] == ["bench.window",
                                                          "stage2"]
    r = trace_metrics.reduce(tr)
    assert r["busy_s"] == pytest.approx(0.004)
    assert r["kernel_s"] == pytest.approx({"stage2_score_pallas": 0.004})
    assert r["program_s"]["stage2"] == pytest.approx(0.006)


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("stage2"):
                f(x).block_until_ready()
            with TraceAnnotation("gen.wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    tr = trace_metrics.load(str(tmp_path), layer_spans.SPAN_NAMES
                            + (trace_metrics.WINDOW_SPAN,))
    r = trace_metrics.reduce(tr)
    assert r["spans"]["stage2"]["count"] == 3
    assert r["spans"]["gen.wait"]["total_s"] >= 0.006
    idle = dict(r["idle_gaps"])
    assert idle["gen.wait"] >= 0.006
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])


# --------------------------------------------------------------- the counts
def _small_orders(seed=3):
    params = copy.deepcopy(traffic_gen.load_traffic("refresh-shared-ip"))
    params["population"] = {"users": 30,
                            "shared_ip_groups": [{"groups": 2, "users": 3}],
                            "nat_pool": {"users": 8, "ips": 2}}
    params["history"] = {"days": 3, "orders_per_user": 2}
    s = traffic_gen.generate(params, 48, seed, 1.0)
    return traffic_gen.Orders.concat([s.history, s.prime])


def test_stage1_count_is_the_same_for_the_one_hot_kernel_and_a_gather():
    from repro.core.dds import IncrementalDDSBuilder
    from repro.core.graph import pad_graph

    ref = correctness.load_reference(cell.find_config("lnn-gat"))
    orders = _small_orders()
    b = IncrementalDDSBuilder(48, max_history=8)
    for i in range(len(orders)):
        b.add_order(orders.entities[i].tolist(), int(orders.snapshot[i]),
                    orders.features[i])
    dds = b.build()
    budget = traffic_gen.pow2_bin(dds.coo.num_nodes)
    pg = pad_graph(dds.coo, num_nodes=budget, max_deg=32)
    kernel_graph = layer_spans._graph_size(pg)          # what the chip ran
    g = ref.Stage1Graph(orders.snapshot, orders.entities, orders.features,
                        max_history=8, max_deg=32)      # the plain gather's
    assert kernel_graph == (g.num_nodes, len(g.src))
    model = cell.find_config("lnn-gat")["service"]["model"]
    assert work_counts.stage1_graph(model, *kernel_graph) == \
        work_counts.stage1_graph(model, g.num_nodes, len(g.src))


@pytest.mark.parametrize("gnn", ["gcn", "gat"])
def test_every_share_stays_under_100_percent_at_the_tested_shapes(gnn):
    """The algorithm's least time never exceeds the least time of the work
    the kernels actually do, so no measured share can pass 100%."""
    model = cell.find_config(f"lnn-{gnn}")["service"]["model"]
    peak = PEAKS["TPU v5 lite"]
    H, D = model["hidden_dim"], 32
    for budget in (64, 4096, 32768):
        nodes, edges = budget // 2 + 1, (budget // 2 + 1) * 12
        alg = work_counts.stage1_graph(model, nodes, edges)
        # what the kernels do: the same transforms over the padded bin, and
        # per layer and aggregation a [N, N] one-hot block built over D
        # slots and multiplied into [N, H]
        pad_ops, pad_bytes = work_counts.stage1_graph(model, budget, budget * D)
        n_agg = 4 if gnn == "gcn" else 1
        impl_ops = pad_ops + 2.0 * 2 * n_agg * budget * budget * (D + H)
        assert alg[0] <= impl_ops and alg[1] <= pad_bytes
        assert work_counts.roofline_s(*alg, peak) <= \
            work_counts.roofline_s(impl_ops, pad_bytes, peak)
    for n in (1, 2, 9, 16):
        ops, byts = work_counts.stage2_flush(model, n, 7)
        bucket = 16 if n > 8 else max(2, 1 << (n - 1).bit_length())
        ops_b, byts_b = work_counts.stage2_flush(model, bucket, 8)
        assert work_counts.roofline_s(ops, byts, peak) <= \
            work_counts.roofline_s(ops_b, byts_b, peak)


# ------------------------------------------------------------ correctness
def test_control_at_the_lower_precision_is_not_correct():
    """The reference at three bf16 passes, put in the program's place,
    fails the configuration's limits; at float32 it passes them."""
    config = cell.find_config("lnn-gcn")
    ref = correctness.load_reference(config)
    orders = _small_orders(seed=8)
    n_hist = len(orders) - 40
    args = (ref, config, 8, orders, n_hist, [])
    exp = correctness.Expected(*args)
    low = correctness.Expected(*args, precision="high")
    same = correctness.control_observed(exp, 40)
    ok, _ = correctness.judge(correctness.compare(same, exp, False),
                              config["limits"])
    assert ok
    ok, checks = correctness.judge(
        correctness.compare(correctness.control_observed(low, 40), exp, False),
        config["limits"])
    assert not ok, checks


def test_a_sound_tiny_run_is_correct():
    r = run_tiny("lnn-gcn.serve-poisson", trace=True)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 60 and r["failed"] == 0
    assert set(r["metrics"]) >= {"ingest_us_per_order.lat", "gc_pause_ms.lat",
                                 "kv_gather_us_per_order.lat",
                                 "orders_per_flush.lat", "gen_late_p95_ms.lat"}
    assert list(r)[-1] == "checks"


@pytest.fixture
def answer_altered(monkeypatch):
    from repro.stream.workers import Stage2Scorer

    score = Stage2Scorer._score

    def altered(self, *a):
        probs, stale, version = score(self, *a)
        probs = probs.copy()
        probs[0] = np.float32(1.0) - probs[0]
        return probs, stale, version

    monkeypatch.setattr(Stage2Scorer, "_score", altered)


@pytest.fixture
def half_the_slots_left_out(monkeypatch):
    from repro.serve.kvstore import KVStore

    lookup = KVStore.lookup_batch_versioned

    def half(self, lists, k_max, **kw):
        emb, mask, stale = lookup(self, lists, k_max, **kw)
        mask[:, : k_max // 2] = 0.0
        emb[:, : k_max // 2] = 0.0
        return emb, mask, stale

    monkeypatch.setattr(KVStore, "lookup_batch_versioned", half)


@pytest.fixture
def refresh_writes_dropped(monkeypatch):
    from repro.serve.kvstore import KVStore

    put = KVStore.put_batch

    def drop(self, keys, values, *a, **kw):
        keys, values = list(keys), list(values)
        return put(self, keys[::2], values[::2], *a, **kw)

    monkeypatch.setattr(KVStore, "put_batch", drop)


def test_an_answer_altered_where_it_is_produced_is_not_correct(answer_altered):
    r = run_tiny("lnn-gcn.serve-poisson")
    assert not r["correct"]
    assert r["checks"]["score_gap"]["value"] > r["checks"]["score_gap"]["limit"]


def test_half_of_the_kv_slots_left_out_is_not_correct(half_the_slots_left_out):
    r = run_tiny("lnn-gcn.serve-backlog", seconds=0.5)
    assert not r["correct"]
    assert r["checks"]["key_mismatch"]["value"] > 0


def test_refresh_writes_left_out_are_not_correct(refresh_writes_dropped):
    r = run_tiny("lnn-gat.refresh-shared-ip", seconds=1.0, rate=30.0)
    assert not r["correct"]
    assert r["checks"]["write_missing"]["value"] > 0
