#!/usr/bin/env python3
"""Chip smoke run: the streaming Lambda loop end to end on one TPU.

Drives the served path once through the entry points a user calls, at the
paper model's full width (``repro.configs.lnn_fraud.SERVICE``: gcn, 3
layers, H=64, MLP (64, 32), k_max=8, max_batch=16, community_size=4096)
with the fused Pallas kernels on (``model.use_pallas=True``) and random
weights from ``lnn_init(PRNGKey(seed))``:

1. replays a seeded synthetic checkout stream through ``FraudService`` in
   streaming mode with 4 inline workers; the stream is sized so that every
   micro-batch bucket 2..16 is flushed and stage-1 refresh bins reach the
   4096-node budget;
2. checks that the served stage-2 and stage-1 programs hold native Mosaic
   kernels (``tpu_custom_call``), not the interpreter;
3. compares the chip's stage-1 rows and scores with a plain float32
   reference: the unfused jnp path on the host CPU at highest matmul
   precision, fed the same padded graphs and the same stream;
4. replays with 1 worker and reports whether its scores are bit-identical
   to the 4-worker run (a finding, not a failure condition);
5. boots the HTTP gateway on an ephemeral port and checks that scores over
   the wire equal in-process scores.

It refuses to run, before any phase, unless JAX's first device is a TPU.
Every line but the last is a report; phase times are wall times, not a
benchmark.  The last line, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Run:  python chip_smoke.py [--seed 0] [--users 2000]
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# the float32 reference runs on the host CPU beside the chip: a platform
# list that names only the accelerator keeps it first and adds the CPU
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.lnn_fraud import SERVICE  # noqa: E402
from repro.core import lnn_init, lnn_stage1  # noqa: E402
from repro.data import SynthConfig, generate_event_stream  # noqa: E402
from repro.gateway import serve_gateway  # noqa: E402
from repro.service import FraudService  # noqa: E402
from repro.stream.microbatch import bucket_size  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

#: Bounds against the float32 reference.  Both sides compute in f32 with
#: full-precision matmuls, so they differ only by summation order and the
#: chip's multi-pass f32 products, ~1e-6; the bounds leave two orders of
#: magnitude of headroom while a single bf16 pass (relative error ~4e-3)
#: or a wrong gather would exceed them.
SCORE_ATOL = 1e-4           # |chip - reference| of a fraud probability
STAGE1_RTOL = 1e-4          # |chip - reference| of a stage-1 row / max |row|

#: Poisson arrival rate: ~8 requests per worker per 5 ms deadline, so the
#: four workers flush every bucket from 2 (quiet spells) to 16 (size
#: trigger)
RATE_PER_S = 6000.0
NUM_WORKERS = 4
GATEWAY_EVENTS = 48

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _say(*args) -> None:
    print(*args, flush=True)


class PhaseFailed(Exception):
    """A phase ran but its result is wrong."""


def require_tpu() -> dict:
    """The device JAX sees, or SystemExit if it is not a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX's first device is {dev.platform!r}, not a TPU; "
            "this script has no CPU fallback")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def make_stream(seed: int, users: int, rate_per_s: float = RATE_PER_S):
    """Seeded synthetic checkout stream -> (events, raw feature width)."""
    events, g, _ = generate_event_stream(
        SynthConfig(num_users=users, seed=seed), rate_per_s=rate_per_s)
    return events, int(g.order_features.shape[1])


def service_config(feat_dim: int, num_workers: int = NUM_WORKERS,
                   use_pallas: bool = True):
    """The canonical serving artifact with the fused kernels on."""
    return SERVICE.replace(
        model={"use_pallas": use_pallas, "feat_dim": feat_dim},
        engine={"num_workers": num_workers}, gateway={"port": 0})


def replay(config, params, events) -> dict:
    """One streaming replay through ``FraudService``.  Returns the scores by
    order id, the buckets flushed, and every stage-1 refresh launch as
    ``(padded graph, rows)``."""
    svc = FraudService(config, params=params).build()
    refresher = svc.engine.refresher
    launches: list = []
    run_stage1 = refresher._run_stage1

    def recording(pgs, *args):
        hs = run_stage1(pgs, *args)
        launches.extend(zip(pgs, hs))
        return hs

    refresher._run_stage1 = recording
    try:
        report = svc.replay(events)
    finally:
        svc.close()
    max_batch = config.engine.max_batch
    return {
        "scores": report.scores_by_order(),
        "buckets": sorted({bucket_size(r.batch_size, max_batch)
                           for r in report.results}),
        "launches": launches,
        "scorer": svc.engine.pool.workers[0].scorer,
        "stage1": refresher._stage1,
        "params": svc.model_params(),
    }


def kernels_native(run: dict, config) -> dict:
    """Lower the replay's own stage-2 (largest bucket) and stage-1 (largest
    refresh bin) programs and report whether each holds a Mosaic kernel."""
    eng = config.engine
    lnn = config.to_lnn_config()
    b = eng.max_batch
    scorer = run["scorer"]
    stage2 = scorer._stage2.lower(
        run["params"], np.zeros((b, eng.k_max, lnn.hidden_dim), np.float32),
        np.zeros((b, eng.k_max), np.float32),
        np.zeros((b, lnn.feat_dim), np.float32), None).compile().as_text()
    pg = max((pg for pg, _ in run["launches"]),
             key=lambda g: g.features.shape[0])
    stage1 = run["stage1"].lower(run["params"], pg).compile().as_text()
    return {"stage2_native": "tpu_custom_call" in stage2,
            "stage1_native": "tpu_custom_call" in stage1,
            "stage1_nodes": int(pg.features.shape[0])}


def _on_host_cpu():
    """Context for the float32 reference: host CPU, highest precision."""
    stack = contextlib.ExitStack()
    stack.enter_context(jax.default_device(jax.devices("cpu")[0]))
    stack.enter_context(jax.default_matmul_precision("highest"))
    return stack


def stage1_reference(run: dict, config) -> dict:
    """Max difference of every captured stage-1 launch from the unfused
    jnp stage 1 on the host CPU, relative to the largest reference row."""
    lnn = dataclasses.replace(config.to_lnn_config(), use_pallas=False)
    diff, scale, rows = 0.0, 0.0, 0
    with _on_host_cpu():
        cpu = jax.devices("cpu")[0]
        params = jax.device_put(run["params"], cpu)
        ref_fn = jax.jit(lambda p, g: lnn_stage1(p, lnn, g))
        for pg, h in run["launches"]:
            ref = np.asarray(ref_fn(params, jax.device_put(pg, cpu)))
            if not np.isfinite(h).all():
                raise PhaseFailed("non-finite stage-1 rows on the chip")
            diff = max(diff, float(np.abs(np.asarray(h) - ref).max()))
            scale = max(scale, float(np.abs(ref).max()))
            rows += ref.shape[0]
    return {"max_abs": diff, "max_ref": scale, "rows": rows,
            "rel": diff / max(scale, 1e-30)}


def score_reference(run: dict, config, events) -> dict:
    """Replay the same stream through the unfused path on the host CPU and
    compare every order's probability."""
    with _on_host_cpu():
        ref = replay(config.replace(model={"use_pallas": False}),
                     jax.device_put(run["params"], jax.devices("cpu")[0]),
                     events)["scores"]
    chip = run["scores"]
    if set(chip) != set(ref):
        raise PhaseFailed("chip and reference scored different orders")
    vals = np.asarray([chip[o] for o in ref], np.float64)
    if not np.isfinite(vals).all():
        raise PhaseFailed("non-finite scores on the chip")
    diff = max(abs(chip[o] - ref[o]) for o in ref)
    return {"max_abs": float(diff), "orders": len(ref)}


def replay_parity(run_n: dict, run_1: dict) -> dict:
    """How the N-worker replay's scores compare with the 1-worker one."""
    a, b = run_n["scores"], run_1["scores"]
    common = set(a) & set(b)
    differ = sum(1 for o in common if a[o] != b[o])
    return {"bit_identical": set(a) == set(b) and differ == 0,
            "orders_differing": differ,
            "max_abs": max((abs(a[o] - b[o]) for o in common), default=0.0)}


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        if r.status != 200:
            raise PhaseFailed(f"{url} answered {r.status}")
        return json.loads(r.read())


def gateway_parity(config, params, events) -> dict:
    """Boot ``serve_gateway`` on port 0, POST each event to ``/v1/score``,
    drain, and compare the wire scores bitwise with an in-process run."""
    wire: dict = {}
    gw = serve_gateway(config, params)
    try:
        for ev in events:
            body = _post(gw.url + "/v1/score", {"event": {
                "order_id": ev.order_id, "snapshot": ev.snapshot,
                "entities": list(ev.entities),
                "features": ev.features.tolist(), "arrival": ev.arrival}})
            wire.update((r["order_id"], r["score"]) for r in body["results"])
        body = _post(gw.url + "/admin/drain", {})
        wire.update((r["order_id"], r["score"]) for r in body["results"])
    finally:
        gw.close()
        gw.service.close()
    svc = FraudService(config, params=params).build().warmup()
    try:
        local = []
        for ev in events:
            local.extend(svc.submit(ev))
        local.extend(svc.drain())
    finally:
        svc.close()
    ref = {r.request.tag.order_id: r.score for r in local}
    if set(wire) != set(ref):
        raise PhaseFailed("the gateway scored different orders")
    differ = sum(1 for o in ref if wire[o] != ref[o])
    return {"requests": len(events), "orders": len(ref),
            "wire_equal": differ == 0, "orders_differing": differ}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--users", type=int, default=2000,
                    help="synthetic users in the replayed stream")
    args = ap.parse_args(argv)

    device = require_tpu()
    cache = enable_compile_cache()
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: compiles.__setitem__(
            0, compiles[0] + (event == _COMPILE_EVENT)))
    _say(f"device: {device} jax {jax.__version__}; compile cache {cache}")

    from repro.kernels.ops import _interpret

    failures: list = []
    state: dict = {}

    def phase(name, fn):
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception as e:  # noqa: BLE001 - every phase is reported
            failures.append(f"{name}: {type(e).__name__}: {e}")
            _say(f"phase {name}: FAILED\n{traceback.format_exc()}")
            return None
        _say(f"phase {name}: {time.perf_counter() - t0:.3f} s wall "
              f"(not a benchmark); {compiles[0]} compiles so far")
        return out

    def check(ok: bool, what: str):
        if not ok:
            failures.append(what)
            _say(f"check FAILED: {what}")

    def setup():
        events, feat_dim = make_stream(args.seed, args.users)
        config = service_config(feat_dim)
        params = lnn_init(jax.random.PRNGKey(args.seed),
                          config.to_lnn_config())
        state.update(events=events, config=config, params=params)
        return len(events)

    n_events = phase("setup", setup)
    if n_events is None:
        return 1
    events, config, params = state["events"], state["config"], state["params"]
    _say(f"stream: {n_events} events, seed {args.seed}, {args.users} users; "
          f"pallas interpret mode: {_interpret()}")
    check(not _interpret(), "Pallas kernels would run in interpret mode")

    run = phase(f"replay_n{NUM_WORKERS}",
                lambda: replay(config, params, events))
    if run is None:
        return _finish(failures, device)
    budgets = sorted({int(pg.features.shape[0]) for pg, _ in run["launches"]})
    _say(f"buckets flushed: {run['buckets']}; stage-1 launches: "
          f"{len(run['launches'])}, padded bin sizes {budgets}")
    check(set(run["buckets"]) >= {2, 4, 8, 16},
          f"not every bucket 2..16 was flushed: {run['buckets']}")
    check(config.refresh.community_size in budgets,
          f"no refresh bin reached {config.refresh.community_size} nodes")

    native = phase("kernels_native", lambda: kernels_native(run, config))
    if native is not None:
        _say(f"kernels: {native}")
        check(native["stage2_native"], "stage 2 has no native TPU kernel")
        check(native["stage1_native"], "stage 1 has no native TPU kernel")

    s1 = phase("stage1_reference", lambda: stage1_reference(run, config))
    if s1 is not None:
        _say(f"stage-1 rows vs f32 reference: {s1} (bound rel "
              f"{STAGE1_RTOL})")
        check(s1["rel"] <= STAGE1_RTOL, "stage-1 rows beyond the bound")

    sc = phase("score_reference",
               lambda: score_reference(run, config, events))
    if sc is not None:
        _say(f"scores vs f32 reference: {sc} (bound {SCORE_ATOL})")
        check(sc["max_abs"] <= SCORE_ATOL, "scores beyond the bound")

    run1 = phase("replay_n1", lambda: replay(
        config.replace(engine={"num_workers": 1}), params, events))
    if run1 is not None:
        _say(f"finding: N=1 vs N={NUM_WORKERS} replay on the chip: "
              f"{replay_parity(run, run1)}")

    gw = phase("gateway", lambda: gateway_parity(
        config, params, events[:GATEWAY_EVENTS]))
    if gw is not None:
        _say(f"gateway: {gw}")
        check(gw["wire_equal"], "wire scores differ from in-process scores")
    return _finish(failures, device)


def _finish(failures: list, device: dict) -> int:
    if failures:
        _say(f"FAILED: {len(failures)} problem(s): {failures}")
        return 1
    _say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
