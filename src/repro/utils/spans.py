"""Program spans — named host intervals at the served path's layer boundaries.

Each span is a ``jax.profiler.TraceAnnotation``: an operator who takes a
profile with spans on sees, in the same trace and on the same clock as the
device's operations, which layer of the program the host was in while the
device sat idle::

    from repro.utils import spans
    spans.enable(True)
    with jax.profiler.trace("/tmp/profile"):
        ...                      # serve traffic
    spans.enable(False)

Spans are off by default.  Off, :func:`span` returns one shared no-op
context object: no annotation is built and no string is formatted, so the
cost on the hot path is one call and an empty ``with``.  On, only names in
:data:`SPANS` may open (a typo is an error, as in ``utils.crashpoint``).

The module also counts XLA compiles: one process-wide ``jax.monitoring``
listener on the backend-compile event, registered by the first
:func:`enable` or :func:`install_compile_counter` (``FraudService.build``
calls it).  It costs nothing until a compile happens.  While spans are on,
each compile is also charged to the innermost program span open on the
compiling thread (:func:`compiles_by_span`), so a compile inside the served
path names the layer that triggered it.

Like ``utils.crashpoint`` this is a dependency-free leaf: ``serve.*`` and
``stream.*`` both import it, and JAX is imported only when spans are first
switched on or the counter is installed.
"""
from __future__ import annotations

import threading

#: every registered span, outermost first along the served path, then the
#: batch-layer refresh.  ``docs/streaming.md`` ("Tracing") says what each
#: covers.
SPANS = (
    "service.submit",      # FraudService.submit, the whole call
    "ingest.order",        # StreamIngester.ingest
    "ingest.keys",         # its speed-layer keys (builder.entity_keys)
    "ingest.dds",          # its DDS growth (builder.add_order)
    "ingest.partition",    # its community update and dirty-pair marks
    "batch.flush",         # MicroBatcher.flush, from the popped batch on
    "batch.assemble",      # the padded features and key lists
    "kv.lookup",           # KVStore.lookup_batch_versioned
    "s2.slot_dt",          # per-slot time gaps (hgt models only)
    "s2.launch",           # the jitted stage-2 call, result left on device
    "s2.sync",             # waiting for it and the device-to-host copy
    "s2.tail",             # host sigmoid (or the GBDT head) and staleness
    "batch.results",       # ScoredResult construction
    "refresh",             # RefreshDriver.refresh, one snapshot close
    "refresh.snapshot",    # dirty pairs, bin packing, subgraph build
    "refresh.pad",         # pad_graph of every bin
    "refresh.stage1",      # stage-1 launches and rows back on the host
    "refresh.put",         # row resolve and KVStore.put_batch
)

#: the key :func:`compiles_by_span` uses for a compile outside any span
OUTSIDE = "(none)"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_KNOWN = frozenset(SPANS)


class _Off:
    """The shared no-op span returned while spans are off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


OFF = _Off()

_on = False
_annotation = None          # jax.profiler.TraceAnnotation, once imported
_local = threading.local()  # .stack: names of the open spans, this thread
_lock = threading.Lock()
_listening = False
_compiles = 0
_by_span: dict[str, int] = {}


class _Span:
    """One open program span: the profiler annotation plus an entry on the
    thread's stack of open names, which charges compiles to it."""

    __slots__ = ("name", "_ta")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self._ta = _annotation(name, **meta)

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self.name)
        self._ta.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._ta.__exit__(exc_type, exc, tb)
        _local.stack.pop()
        return False


def enable(on: bool) -> None:
    """Switch program spans on or off for the whole process."""
    global _on, _annotation
    if on:
        install_compile_counter()
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
    _on = bool(on)


def span(name: str, **meta):
    """A context manager over the program span ``name``.  ``meta`` becomes
    the event's metadata in the trace (the event keeps the bare name)."""
    if not _on:
        return OFF
    if name not in _KNOWN:
        raise ValueError(f"unknown span {name!r}; registered: {SPANS}")
    return _Span(name, meta)


def _on_compile(event: str, _secs: float, **_kw) -> None:
    global _compiles
    if event != COMPILE_EVENT:
        return
    with _lock:
        _compiles += 1
        if _on:
            stack = getattr(_local, "stack", None)
            key = stack[-1] if stack else OUTSIDE
            _by_span[key] = _by_span.get(key, 0) + 1


def install_compile_counter() -> None:
    """Register the compile listener once per process (idempotent)."""
    global _listening
    with _lock:
        if _listening:
            return
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _listening = True


def compiles() -> int:
    """XLA compiles in this process since the counter was installed."""
    return _compiles


def compiles_by_span() -> dict[str, int]:
    """Compiles made while spans were on, by the innermost program span
    open then (:data:`OUTSIDE` where none was)."""
    with _lock:
        return dict(_by_span)


__all__ = ["OFF", "OUTSIDE", "SPANS", "compiles", "compiles_by_span",
           "enable", "install_compile_counter", "span"]
