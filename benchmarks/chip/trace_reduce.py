"""Reduce a ``jax.profiler`` trace of a window to the device's busy time,
its busiest operations, and its idle gaps labelled by what the host was
doing.

``load_events`` reads the ``.xplane.pb`` file with nothing but JAX and
keeps two things: the operation events of each TPU's ``XLA Ops`` line,
each named by its program (the ``XLA Modules`` event it falls in) and
instruction, and the host annotations whose names start with
``bench:``. ``reduce``
works on that plain form, so it can be checked on a small recorded
trace.

Clocks: the profiler's timestamps and the host spans' monotonic
nanoseconds differ by an offset, read from the ``bench:align`` annotation
whose monotonic start the benchmark recorded.
"""
from __future__ import annotations

import bisect
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def op_name(module: str, hlo: str) -> str:
    """``<program>/<instruction>``: the XLA Ops line names an operation by
    its whole HLO text; keep the instruction's name and, for a custom
    call, its target."""
    short = hlo.split(" = ", 1)[0]
    if "custom_call_target=" in hlo:
        short += ":" + hlo.split('custom_call_target="', 1)[1].split('"')[0]
    return f"{module}/{short}"


def load_events(log_dir: Path) -> dict:
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    device: Dict[str, List[Tuple[str, float, float]]] = {}
    marks: Dict[str, List[Tuple[float, float]]] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = [(e.start_ns, e.name) for e in lines.get(MODULES_LINE, [])]
            starts = [s for s, _ in mods]
            ops = []
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                ops.append((op_name(mods[i][1] if i >= 0 else "",
                                    e.name), e.start_ns, e.duration_ns))
            device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        marks.setdefault(e.name, []).append(
                            (e.start_ns, e.duration_ns))
    return {"device": device, "marks": marks}


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a: Sequence[Sequence[float]], b: Sequence[Sequence[float]]
            ) -> float:
    """The length of the intersection of two lists of disjoint sorted
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def spans_from_traces(traces) -> List[Tuple[int, int, str, int]]:
    """``(t0, t1, name, depth)`` of every span of the given query traces
    (``repro.obs`` QueryTrace objects), on the host's monotonic clock."""
    out = []
    for tr in traces:
        by_id = {(sp.rank, sp.id): sp for sp in tr.spans}
        for sp in tr.spans:
            depth, p = 0, sp.parent
            while p is not None:
                depth += 1
                p = by_id[(sp.rank, p)].parent
            out.append((sp.t0, sp.t1, sp.name, depth))
    return out


def _labels(times: Sequence[float], spans) -> List[str]:
    """For each time, the deepest host span open then (the latest-started
    on a tie); ``client`` where no query was in flight."""
    edges = sorted([(t0, 1, i) for i, (t0, t1, _, _) in enumerate(spans)]
                   + [(t1, 0, i) for i, (t0, t1, _, _) in enumerate(spans)])
    order = sorted(range(len(times)), key=lambda k: times[k])
    out = ["client"] * len(times)
    active: Dict[int, Tuple[int, int]] = {}
    j = 0
    for k in order:
        t = times[k]
        while j < len(edges) and edges[j][0] <= t:
            _, opening, i = edges[j]
            if opening:
                active[i] = (spans[i][3], spans[i][0])
            else:
                active.pop(i, None)
            j += 1
        if active:
            out[k] = spans[max(active, key=active.get)][2]
    return out


def reduce(events: dict, align_mono: int, window: Tuple[int, int],
           spans: Sequence[Tuple[int, int, str, int]]) -> dict:
    """Busy time (the union of operation intervals, averaged over the
    chips traced), the length of the window, the operations that took the
    most device time, the idle time grouped by the host span it fell in,
    and each chip's busy intervals on the host's clock (``busy``, for the
    readers that ask how much device time fell inside given host spans).
    ``window`` and ``spans`` are on the host's monotonic clock."""
    if "bench:align" not in events["marks"]:
        raise ValueError("the trace has no bench:align annotation")
    offset = events["marks"]["bench:align"][0][0] - align_mono
    w0, w1 = window[0] + offset, window[1] + offset
    if not events["device"] or w1 <= w0:
        return None
    busy_total = 0.0
    op_time: Dict[str, float] = {}
    gaps_by: Dict[str, float] = {}
    busy_host: Dict[str, List[List[float]]] = {}
    for plane in sorted(events["device"]):
        clipped = []
        for name, s, d in events["device"][plane]:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                op_time[name] = op_time.get(name, 0.0) + (b - a)
        busy = union(clipped)
        busy_total += sum(b - a for a, b in busy)
        busy_host[plane] = [[a - offset, b - offset] for a, b in busy]
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        labels = _labels([(a + b) / 2 - offset for a, b in gaps], spans)
        for (a, b), label in zip(gaps, labels):
            gaps_by[label] = gaps_by.get(label, 0.0) + (b - a)
    n = len(events["device"])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_ns": busy_total / n, "window_ns": w1 - w0,
            "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
            "idle_gaps": [[k, v / n / 1e9] for k, v in top_gaps],
            "busy": busy_host}
