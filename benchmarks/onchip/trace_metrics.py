"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` a ``jax.profiler`` trace writes, with JAX alone:

* device busy time: the union of the intervals in which an operation ran
  on the device (the ``XLA Ops`` line of each ``/device:`` plane);
* kernel time by stable name: an op's name without its HLO text and
  numeric suffix (``%stage2_score_pallas.1 = ...`` -> ``stage2_score_pallas``);
* idle-gap attribution: each interval of the window in which the device
  ran nothing is charged to the innermost benchmark span open on the host
  at that time, or to ``host.other`` where none is.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

OTHER = "host.other"
WINDOW_SPAN = "bench.window"


@dataclass
class Trace:
    host: list = field(default_factory=list)     # (name, start_ns, end_ns)
    ops: list = field(default_factory=list)      # (name, start_ns, end_ns)
    modules: list = field(default_factory=list)  # (name, start_ns, end_ns)
    devices: int = 0


def stable_name(op: str) -> str:
    """``%stage2_score_pallas.1 = f32[8] custom-call(...)`` ->
    ``stage2_score_pallas``: the op's name without its HLO text, ``%`` or
    numeric suffix."""
    name = op.split(" = ", 1)[0].strip().lstrip("%_")
    return re.sub(r"(\.\d+)+$", "", name)


def load(trace_dir: str, span_names) -> Trace:
    """Host spans named in ``span_names`` and every device op of the
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_planes(ProfileData.from_file(files[-1]).planes, span_names)


def from_planes(planes, span_names) -> Trace:
    """The same from the planes of a profile."""
    tr = Trace()
    names = set(span_names)
    for plane in planes:
        if plane.name.startswith("/device:"):
            # a chip is a device plane with an ``XLA Ops`` line; others (a
            # ``/device:CUSTOM:...`` plane) run no op and are not counted
            n_ops = len(tr.ops)
            for line in plane.lines:
                dest = {"XLA Ops": tr.ops, "XLA Modules": tr.modules}.get(
                    line.name)
                if dest is not None:
                    dest.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events)
            tr.devices += len(tr.ops) > n_ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                tr.host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events if e.name in names)
    tr.host.sort(key=lambda s: (s[1], -s[2]))
    tr.ops.sort(key=lambda s: s[1])
    tr.modules.sort(key=lambda s: s[1])
    return tr


def union(intervals) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def innermost_segments(spans, lo, hi) -> list:
    """Flatten nested host spans into ``(start, end, name)`` segments that
    cover ``[lo, hi]``, each named by its innermost open span (None where
    no span is open)."""
    segs: list = []
    stack: list = []
    cur = lo

    def advance(t):
        nonlocal cur
        t = min(max(t, lo), hi)
        if t > cur:
            segs.append((cur, t, stack[-1][0] if stack else None))
            cur = t

    for name, s, e in spans:
        while stack and stack[-1][1] <= s:
            advance(stack[-1][1])
            stack.pop()
        advance(s)
        stack.append((name, e))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    advance(hi)
    return segs


def attribute(gaps, segs) -> dict:
    """Seconds of each gap charged to the segment names it overlaps."""
    out: dict = {}
    i = 0
    for gs, ge in gaps:
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < ge:
            s, e, name = segs[j]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                key = name or OTHER
                out[key] = out.get(key, 0) + ov * 1e-9
            j += 1
    return out


def span_totals(spans, lo, hi) -> dict:
    """Per span name: count, total seconds, and self seconds (its time
    minus the time of benchmark spans nested inside it)."""
    tot: dict = {}
    for name, s, e in spans:
        if s >= lo and s < hi:
            d = tot.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
            d["count"] += 1
            d["total_s"] += (e - s) * 1e-9
    for s, e, name in innermost_segments(
            [sp for sp in spans if lo <= sp[1] < hi], lo, hi):
        if name is not None and name in tot:
            tot[name]["self_s"] += (e - s) * 1e-9
    return tot


def program_time(modules, ops, kinds: dict) -> dict:
    """Device seconds of the programs (``XLA Modules`` events) that run a
    kernel of each kind: ``kinds`` maps a kind to name fragments of its
    kernels, e.g. ``{"stage1": ("edge_softmax", "csr_spmm")}``."""
    import bisect

    starts = [o[1] for o in ops]
    out = {k: 0.0 for k in kinds}
    for _, ms, me in modules:
        lo = bisect.bisect_left(starts, ms)
        hi = bisect.bisect_right(starts, me)
        names = {ops[i][0] for i in range(lo, hi)}
        for kind, frags in kinds.items():
            if any(f in n for n in names for f in frags):
                out[kind] += (me - ms) * 1e-9
                break
    return out


#: the programs whose device time the rooflines divide by
PROGRAMS = {"stage2": ("stage2_score",), "stage1": ("edge_softmax", "csr_spmm")}


def reduce(tr: Trace, top: int = 10) -> dict:
    """The window's device numbers.  The window is the ``bench.window``
    host span."""
    win = [s for s in tr.host if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError("trace holds no bench.window span")
    lo, hi = win[0][1], win[0][2]
    spans = [s for s in tr.host if s[0] != WINDOW_SPAN]
    ops = sorted((o for o in tr.ops if o[2] > lo and o[1] < hi),
                 key=lambda o: o[1])
    busy = clip(union([[s, e] for _, s, e in ops]), lo, hi)
    busy_ns = sum(e - s for s, e in busy)
    n_dev = max(tr.devices, 1)
    by_name: dict = {}
    for name, s, e in ops:
        by_name.setdefault(stable_name(name), []).append([s, e])
    # nested events of one name (an op and its parts) count once
    kernel = {k: sum(e - s for s, e in clip(union(iv), lo, hi)) * 1e-9
              for k, iv in by_name.items()}
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    idle = attribute(gaps, innermost_segments(spans, lo, hi))
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_dev,
        "kernel_s": kernel,
        "program_s": program_time(
            [m for m in tr.modules if m[2] > lo and m[1] < hi], ops, PROGRAMS),
        "spans": span_totals(spans, lo, hi),
        "device_ops": sorted(kernel.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
    }
