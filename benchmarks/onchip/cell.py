"""One run of one cell: set-up, the timed window, ``correct``, the metrics.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric lives in its own file, found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json`` (the service configuration as run, its limits and
the name of its plain reference), ``traffic/<traffic>.json`` (the numbers
``traffic_gen`` reads) and ``metrics/<metric>.py`` (one reader each).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import correctness
import layer_spans
import trace_metrics
import traffic_gen

HERE = Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------------------------ lookup
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_config(name: str, root: Path = HERE) -> dict:
    return load_json(root / "configs" / f"{name}.json")


def find_metric_reader(name: str, root: Path = HERE):
    """``metrics/<name>.py``, else the reader of the name's first part
    (``mfu.lat`` -> ``metrics/mfu.py``).  None where neither exists."""
    for stem in (name, name.split(".")[0]):
        path = root / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"onchip_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    return None


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries a run of this cell reports."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if "workloads" not in m or workload in m["workloads"]]
    e2e = {m["name"] for m in cell_metrics(bench, workload, False)}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in e2e)]


# ---------------------------------------------------------------- the run
@dataclass
class Window:
    """What the timed window left behind, for ``correct`` and the metrics."""

    t0: float
    t_stop: float
    t_end: float
    due: np.ndarray
    late: np.ndarray           # generator lateness per submitted order, s
    sent_at: np.ndarray        # perf_counter at each order's send
    sent_cpu: np.ndarray       # the sending thread's CPU time then, s
    answered_at: np.ndarray    # perf_counter of the call that returned it
    responses: list            # (window index, ScoreResponse) in order
    submitted: int
    compiles: int
    stats_before: dict = field(default_factory=dict)
    stats_after: dict = field(default_factory=dict)


def _wait_until(target: float) -> None:
    """Sleep to within half a millisecond of ``target``, then spin."""
    while True:
        left = target - time.perf_counter()
        if left <= 0:
            return
        if left > 6e-4:
            time.sleep(left - 5e-4)


def _service(config: dict):
    from repro.service import ServiceConfig

    return ServiceConfig.from_dict(config["service"])


def _events(orders):
    """The orders as the program's ``CheckoutEvent``; ``arrival`` is each
    order's due time in seconds from the window's start."""
    from repro.stream.events import CheckoutEvent

    due = orders.due
    ents = orders.entities.tolist()
    return [CheckoutEvent(order_id=int(orders.order_id[i]),
                          snapshot=int(orders.snapshot[i]),
                          entities=tuple(ents[i]), features=orders.features[i],
                          label=0.0, arrival=float(due[i]))
            for i in range(len(orders))]


def _stats(svc) -> dict:
    st = svc.stats()
    return {"scored": st.scored, "flushes": st.flushes,
            "refreshes": st.refreshes, "requests": st.requests}


def set_up(config: dict, stream, seed: int, trace: bool):
    """Build the service from the seed, ingest the history and the prime
    order, compile this cell's shapes.  Returns ``(svc, probe)``."""
    import jax

    from repro.core import lnn_init
    from repro.service import FraudService

    service = _service(config)
    lnn = service.to_lnn_config()
    params = jax.jit(lambda k: lnn_init(k, lnn))(jax.random.PRNGKey(int(seed)))
    svc = FraudService(service, params=params).build()
    probe = layer_spans.Probe(svc, trace)
    svc.warmup()
    for ev in _events(stream.history):
        svc.ingest(ev)
    for ev in _events(stream.prime):
        svc.ingest(ev)
    if stream.snapshot_s is not None:
        _warm_stage1(svc, config, stream)
    return svc, probe


def _warm_stage1(svc, config: dict, stream) -> None:
    """Compile the refresh shapes the window can launch: every pow2 bin up
    to the community budget, and the bin of each community over it."""
    from repro.core.graph import COOGraph, pad_graph

    eng = svc.engine
    cs = config["service"]["refresh"]["community_size"]
    all_orders = traffic_gen.Orders.concat(
        [stream.history, stream.prime, stream.window])
    big = {traffic_gen.pow2_bin(n) for n in
           traffic_gen.community_nodes(all_orders).values() if n > cs}
    sizes = sorted({traffic_gen.pow2_bin(1) * 2 ** i
                    for i in range(20) if traffic_gen.pow2_bin(1) * 2 ** i <= cs}
                   | big)
    F = config["service"]["model"]["feat_dim"]
    params = svc.model_params()
    for n in sizes:
        empty = COOGraph(num_nodes=1, src=np.zeros(0, np.int64),
                         dst=np.zeros(0, np.int64), etype=np.zeros(0, np.int32),
                         features=np.zeros((1, F), np.float32),
                         node_type=np.zeros(1, np.int32),
                         snapshot=np.zeros(1, np.int32),
                         label=np.zeros(1, np.float32),
                         label_mask=np.zeros(1, np.float32))
        pg = pad_graph(empty, num_nodes=n,
                       max_deg=config["service"]["engine"]["max_deg"])
        np.asarray(eng.refresher._stage1(params, pg))


def run_window(svc, probe, stream, events, seconds: float,
               backlog: bool) -> Window:
    """Drive ``FraudService.submit`` open loop: each order is sent at its
    due time (all at once for a backlog) and timed from it to the return
    of the call that hands back its response."""
    import jax

    due = stream.window.due
    n = len(events)
    pos = {int(o): i for i, o in enumerate(stream.window.order_id)}
    late = np.full(n, np.nan)
    sent_at = np.full(n, np.nan)
    sent_cpu = np.full(n, np.nan)
    answered_at = np.full(n, np.nan)
    responses: list = []
    compiles = [0]

    def on_compile(event, _secs, **_):
        compiles[0] += event == COMPILE_EVENT

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    before = _stats(svc)
    probe.recording = True
    submitted = 0
    t0 = time.perf_counter()
    with probe.window():
        for i in range(n):
            target = t0 + due[i]
            if backlog:
                if time.perf_counter() - t0 >= seconds:
                    break
            elif time.perf_counter() < target:
                with probe.wait():
                    _wait_until(target)
            sent_at[i] = time.perf_counter()
            sent_cpu[i] = time.thread_time()
            late[i] = sent_at[i] - target
            out = svc.submit(events[i])
            submitted += 1
            now = time.perf_counter()
            for r in out:
                j = pos[r.request.tag.order_id]
                answered_at[j] = now
                responses.append((j, r))
        t_stop = time.perf_counter()
        out = svc.drain()
        t_end = time.perf_counter()
        for r in out:
            j = pos[r.request.tag.order_id]
            answered_at[j] = t_end
            responses.append((j, r))
    probe.recording = False
    compiles_in_window = compiles[0]
    return Window(t0=t0, t_stop=t_stop, t_end=t_end, due=due,
                  late=late[:submitted], sent_at=sent_at[:submitted],
                  sent_cpu=sent_cpu[:submitted], answered_at=answered_at,
                  responses=responses, submitted=submitted,
                  compiles=compiles_in_window, stats_before=before,
                  stats_after=_stats(svc))


def observed(probe, win: Window, k_max: int, store) -> correctness.Observed:
    """Align each recorded flush with the responses it produced."""
    idx, ke, kt, masks, embs, probs = [], [], [], [], [], []
    p = 0
    for lists, emb, mask, _ in probe.flushes:
        if p >= len(win.responses):
            break
        nreal = win.responses[p][1].batch_size
        for i in range(nreal):
            j, r = win.responses[p + i]
            keys = list(lists[i])
            if keys != list(r.request.entity_keys):
                raise ValueError("a flush's rows do not match its responses")
            e = np.zeros(k_max, np.int64)
            t = np.zeros(k_max, np.int64)
            for s, (ent, tt) in enumerate(keys[:k_max]):
                e[s], t[s] = ent, tt
            idx.append(j)
            ke.append(e)
            kt.append(t)
            probs.append(r.score)
        masks.append(mask[:nreal])
        embs.append(emb[:nreal])
        p += nreal
    w_ent, w_t, w_rows = probe.written(store)
    H = embs[0].shape[-1] if embs else 0
    return correctness.Observed(
        index=np.asarray(idx, np.int64),
        keys_ent=np.asarray(ke, np.int64).reshape(-1, k_max),
        keys_t=np.asarray(kt, np.int64).reshape(-1, k_max),
        mask=np.concatenate(masks) if masks else np.zeros((0, k_max)),
        emb=np.concatenate(embs) if embs else np.zeros((0, k_max, H)),
        prob=np.asarray(probs, np.float32),
        write_ent=w_ent, write_t=w_t, write_rows=w_rows,
        attempted=win.submitted)


def reference_orders(stream, submitted: int):
    """Every order the service ingested, in arrival order."""
    return traffic_gen.Orders.concat(
        [stream.history, stream.prime, stream.window.take(submitted)])


def closed_in_window(stream, submitted: int) -> list:
    """Snapshots the window closed (those before the last one reached)."""
    if stream.snapshot_s is None or submitted == 0:
        return []
    last = int(stream.window.snapshot[submitted - 1])
    return list(range(stream.first_window_snapshot, last))


# ------------------------------------------------------------- metrics
@dataclass
class Context:
    """What a per-layer metric reader may read."""

    config: dict
    peak: dict
    orders: int                  # orders answered in the window
    late: np.ndarray             # generator lateness, s
    stats: dict                  # ServiceStats deltas over the window
    flush_sizes: list            # real orders per flush
    slots_per_order: float       # KV slots served per order, mean
    stage1_graphs: list          # (real nodes, real edges) per launch
    trace: dict                  # trace_metrics.reduce output


def end_to_end(win: Window, probe, stream, setup_s: float) -> dict:
    lat = (win.answered_at - (win.t0 + win.due))[~np.isnan(win.answered_at)]
    out = {"setup_s": setup_s}
    if len(lat):
        out["score_p50_ms"] = float(np.percentile(lat, 50) * 1e3)
        out["score_p95_ms"] = float(np.percentile(lat, 95) * 1e3)
        out["score_p99_ms"] = float(np.percentile(lat, 99) * 1e3)
    done = np.count_nonzero(win.answered_at <= win.t_stop)
    out["orders_per_s"] = done / (win.t_stop - win.t0)
    bounds = {snap: b for b, snap in stream.window_closes()}
    stale = [t1 - (win.t0 + bounds[w[1]]) for w, _, t1 in probe.closes
             if w[1] in bounds]
    if stale:
        out["staleness_ms"] = float(np.mean(stale) * 1e3)
    return out


def per_layer(metrics: list, ctx: Context, root: Path = HERE) -> dict:
    out = {}
    for m in metrics:
        reader = find_metric_reader(m["name"], root)
        value = reader(ctx, m) if reader else None
        if value is not None:
            out[m["name"]] = float(value)
    return out


# ------------------------------------------------------------- the cell
def run_cell(bench: dict, workload: dict, config: dict, traffic: dict,
             seed: int, seconds: float, trace: bool, t_start: float,
             rate_per_s: float | None = None, root: Path = HERE,
             peaks: dict | None = None) -> dict:
    """One run; returns the result line's object (``correct`` decided)."""
    import jax

    model = config["service"]["model"]
    stream = traffic_gen.generate(traffic, model["feat_dim"], seed, seconds,
                                  rate_per_s)
    svc, probe = set_up(config, stream, seed, trace)
    program_weights = jax.device_get(svc.model_params())
    events = _events(stream.window)
    backlog = traffic["window"]["arrivals"] == "backlog"
    trace_dir = tempfile.mkdtemp(prefix="onchip_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    try:
        win = run_window(svc, probe, stream, events, seconds, backlog)
    finally:
        if trace:
            jax.profiler.stop_trace()
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    probe.close()
    obs = observed(probe, win, config["service"]["engine"]["k_max"],
                   svc.engine.store)
    flush_sizes = _flush_sizes(win)
    gc_counts = list(probe.gc_collections)
    behind = longest_behind(win, probe.gc_spans,
                            [(t0, t1) for _, t0, t1 in probe.closes])
    e2e = end_to_end(win, probe, stream, setup_s)
    stage1_graphs, closes = list(probe.stage1_graphs), list(probe.closes)
    stats = {k: win.stats_after[k] - win.stats_before[k]
             for k in win.stats_after}
    svc.close()
    del svc, probe
    gc.collect()

    reduced = None
    if trace:
        reduced = trace_metrics.reduce(
            trace_metrics.load(trace_dir, layer_spans.SPAN_NAMES
                               + (trace_metrics.WINDOW_SPAN,)))
        _drop_trace(trace_dir)

    ref = correctness.load_reference(config, root)
    closed = closed_in_window(stream, win.submitted)
    exp = correctness.Expected(ref, config, seed,
                               reference_orders(stream, win.submitted),
                               len(stream.history) + len(stream.prime), closed)
    numbers = correctness.compare(obs, exp, closes=bool(closed))
    weight_gap = max(float(np.abs(np.asarray(a) - b).max() /
                           max(np.abs(b).max(), 1e-30))
                     for a, b in zip(jax.tree.leaves(program_weights),
                                     jax.tree.leaves(exp.params)))
    ok, checks = correctness.judge(numbers, config["limits"])

    metrics = cell_metrics(bench, workload["name"], trace)
    if trace:
        peaks = peaks or load_json(root / "peaks.json")
        ctx = Context(config=config,
                      peak=peaks[dev.device_kind], orders=len(obs.index),
                      late=win.late, stats=stats, flush_sizes=flush_sizes,
                      slots_per_order=float(obs.mask.sum() / max(len(obs.mask), 1)),
                      stage1_graphs=stage1_graphs, trace=reduced)
        values = per_layer(metrics, ctx, root)
    else:
        values = e2e
    result = {
        "correct": bool(ok),
        "attempted": int(win.submitted),
        "failed": int(numbers["unanswered"]),
        "metrics": {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                    for m in metrics if m["name"] in values},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": int(mem)},
    }
    if trace:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    half = len(win.late) // 2
    result["diag"] = {
        "compiles_in_window": win.compiles,
        "weight_gap": weight_gap,
        "gc_collections": gc_counts,
        "gen_late_p95_ms_first_half": _p95_ms(win.late[:half]),
        "gen_late_p95_ms_second_half": _p95_ms(win.late[half:]),
        "gen_late_max_ms": float(np.max(win.late) * 1e3) if len(win.late) else 0,
        "caught_up_share": caught_up_share(win, stream.snapshot_s or 1.0),
        "longest_behind": behind,
        "window_s": win.t_stop - win.t0, "closes": len(closes),
        "refresh_s": [round(t1 - t0, 6) for _, t0, t1 in closes],
        "stats": stats, "end_to_end": e2e,
    }
    result["checks"] = checks
    return result


BEHIND_S = 0.002     # an order sent later than this after its due time


def caught_up_share(win: Window, interval_s: float) -> float:
    """Share of the window's intervals (of ``interval_s`` by due time) in
    which the load generator caught up: sent some order within
    ``BEHIND_S`` of its due time.  A queue that grows all through the
    window catches up in none after it starts growing."""
    if not len(win.late):
        return 0.0
    k = (win.due[:len(win.late)] // interval_s).astype(np.int64)
    caught = [bool(np.min(win.late[k == b]) < BEHIND_S) for b in np.unique(k)]
    return float(np.mean(caught))


def _overlap(lo: float, hi: float, spans) -> float:
    return sum(max(0.0, min(hi, b) - max(lo, a)) for a, b in spans)


def longest_behind(win: Window, gc_spans, refresh_spans) -> dict | None:
    """The longest stretch of consecutive orders the generator sent more
    than ``BEHIND_S`` late, with what took the host's time meanwhile: the
    sending thread's CPU seconds (the rest it was off the CPU: waiting on
    the device, or not scheduled), and the seconds of the interpreter's
    collections and of refreshes inside it."""
    behind = win.late > BEHIND_S
    if not behind.any():
        return None
    edges = np.flatnonzero(np.diff(np.concatenate([[0], behind.view(np.int8),
                                                   [0]])))
    starts, ends = edges[::2], edges[1::2]
    j = int(np.argmax([win.sent_at[e - 1] - win.sent_at[s]
                       for s, e in zip(starts, ends)]))
    s, e = int(starts[j]), int(ends[j])
    # the stretch runs from the last order sent on time to the last late one
    i0 = max(s - 1, 0)
    lo, hi = win.sent_at[i0], win.sent_at[e - 1]
    return {"at_s": float(lo - win.t0), "s": float(hi - lo),
            "orders": e - s, "late_max_ms": float(win.late[s:e].max() * 1e3),
            "cpu_s": float(win.sent_cpu[e - 1] - win.sent_cpu[i0]),
            "gc_s": _overlap(lo, hi, [(a, b) for _, a, b in gc_spans]),
            "refresh_s": _overlap(lo, hi, refresh_spans)}


def _flush_sizes(win: Window) -> list:
    sizes, p = [], 0
    while p < len(win.responses):
        n = win.responses[p][1].batch_size
        sizes.append(n)
        p += n
    return sizes


def _p95_ms(x) -> float:
    return float(np.percentile(x, 95) * 1e3) if len(x) else 0.0


def _drop_trace(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)
