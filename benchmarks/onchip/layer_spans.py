"""Spans and records around the calls into each layer of a built service.

The benchmark does not edit the program: it wraps methods of the service's
own objects, on the instance, for the length of the window.

* ``--trace 1`` runs open a ``jax.profiler.TraceAnnotation`` around each
  call, named by the layer it enters, so that the trace reduction can read
  per-layer time and attribute the device's idle gaps:
  ``engine.ingest``, ``pool.poll``, ``pool.submit``,
  ``store.lookup_batch_versioned``, ``stage2`` (the stage-2 call and the
  host sigmoid in ``Stage2Scorer._score``), ``refresher.on_windows_closed``,
  ``gc.gen0``..``gc.gen2`` (the interpreter's collections), and
  ``gen.wait`` (the load generator's own sleeps).
* Every run records what ``correct`` compares: the KV slots and scores of
  every flush, and each refresh's writes to the KV store.  These are
  references to arrays the program already made, so recording copies
  nothing on the scoring path.  It also records when each of the
  interpreter's collections ran, so that a stall of the load generator can
  be matched against them.
"""
from __future__ import annotations

import gc
import time
from contextlib import nullcontext

import numpy as np

SPAN_NAMES = ("engine.ingest", "pool.poll", "pool.submit",
              "store.lookup_batch_versioned", "stage2",
              "refresher.on_windows_closed", "gc.gen0", "gc.gen1", "gc.gen2",
              "gen.wait")
SNAPSHOT_BITS = 20       # KV key layout: entity << 20 | snapshot


class Probe:
    """Instruments one built ``FraudService`` (streaming, inline worker)."""

    def __init__(self, svc, trace: bool):
        from jax.profiler import TraceAnnotation

        self._ta = TraceAnnotation if trace else None
        self.recording = False
        self.flushes: list = []       # (entity_t_lists, emb, mask, probs)
        self.writes: list = []        # packed KV keys each refresh put
        self.closes: list = []        # (closed (first, last), t0, t1) perf s
        self.stage1_graphs: list = []  # (real nodes, real stage-1 edges)
        self.gc_collections = [0, 0, 0]
        self.gc_spans: list = []      # (generation, t0, t1) perf s
        self._gc_open: list = []
        self._gc_t0 = 0.0
        eng = svc.engine
        pool = eng.pool
        if len(pool.workers) != 1:
            raise ValueError("the benchmark drives one inline worker")
        scorer = pool.workers[0].scorer
        store, refresher = eng.store, eng.refresher
        if trace:
            self._span(eng, "ingest", "engine.ingest")
            self._span(pool, "poll", "pool.poll")
            self._span(pool, "submit", "pool.submit")
            self._span(store, "lookup_batch_versioned",
                       "store.lookup_batch_versioned")
            run_stage1 = refresher._run_stage1

            def stage1(pgs, *a):
                if self.recording:
                    self.stage1_graphs.extend(_graph_size(pg) for pg in pgs)
                return run_stage1(pgs, *a)

            refresher._run_stage1 = stage1
        score = scorer._score
        ta = self._ta

        def scored(params, version, stage2, hybrid, feats, lists, emb, mask,
                   stale):
            with ta("stage2") if ta else nullcontext():
                out = score(params, version, stage2, hybrid, feats, lists,
                            emb, mask, stale)
            if self.recording:
                self.flushes.append((lists, emb, mask, out[0]))
            return out

        scorer._score = scored
        closed = refresher.on_windows_closed

        def on_closed(window):
            t0 = time.perf_counter()
            with ta("refresher.on_windows_closed") if ta else nullcontext():
                r = closed(window)
            if self.recording and window is not None:
                self.closes.append((window, t0, time.perf_counter()))
            return r

        refresher.on_windows_closed = on_closed
        put = store.put_batch

        def put_batch(keys, values, *a, **kw):
            keys = list(keys)
            if self.recording:
                self.writes.append(np.asarray(keys, np.int64))
            return put(keys, values, *a, **kw)

        store.put_batch = put_batch
        gc.callbacks.append(self._on_gc)

    def _span(self, obj, attr: str, name: str) -> None:
        fn = getattr(obj, attr)
        ta = self._ta

        def wrapped(*a, **kw):
            with ta(name):
                return fn(*a, **kw)

        setattr(obj, attr, wrapped)

    def _on_gc(self, phase: str, info: dict) -> None:
        gen = int(info.get("generation", 0))
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            if self.recording:
                self.gc_collections[gen] += 1
            if self._ta is not None:
                span = self._ta(f"gc.gen{gen}")
                span.__enter__()
                self._gc_open.append(span)
            return
        if self.recording:
            self.gc_spans.append((gen, self._gc_t0, time.perf_counter()))
        if self._gc_open:
            self._gc_open.pop().__exit__(None, None, None)

    def wait(self):
        """Context for the generator's sleeps."""
        return self._ta("gen.wait") if self._ta else nullcontext()

    def window(self):
        return self._ta("bench.window") if self._ta else nullcontext()

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def written(self, store):
        """Every key the window's refreshes put, read back from the store
        once the window has closed: ``(entity [m], t [m], rows [m, H])``;
        a key the store does not hold is left out."""
        keys = np.unique(np.concatenate(self.writes)) if self.writes \
            else np.zeros(0, np.int64)
        held, rows = [], []
        for k in keys.tolist():
            entry = store.get_entry(k)
            if entry is not None:
                held.append(k)
                rows.append(entry[0])
        keys = np.asarray(held, np.int64)
        rows = np.stack(rows).astype(np.float32) if rows \
            else np.zeros((0, store.dim), np.float32)
        return keys >> SNAPSHOT_BITS, keys & ((1 << SNAPSHOT_BITS) - 1), rows


def _graph_size(pg) -> tuple[int, int]:
    """The nodes and edges stage 1's algorithm needs in a padded refresh
    graph: shadow and entity vertices (node types 1 and 2; an order's own
    stage-1 row feeds nothing) and the real edges of every type but the
    final hop (type 3, the speed layer's)."""
    nodes = int(np.count_nonzero(np.isin(np.asarray(pg.node_type), (1, 2))))
    m = np.asarray(pg.nbr_mask) > 0
    edges = int(np.count_nonzero(m & (np.asarray(pg.nbr_etype) != 3)))
    return nodes, edges
