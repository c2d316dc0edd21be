"""From a profiler trace to numbers: device busy/idle, top device
operations by self time, idle gaps labelled by the harness's spans.

The pure functions take plain tuples so `test_trace_reduce.py` can feed
them a hand-made event list; `reduce_xplane` reads the `.xplane.pb` that
`jax.profiler` wrote (the reduction idea is `scripts/profile_train.py`'s,
which summed every line of every device plane and so counted a `while`
and its body twice).
"""
from __future__ import annotations

import collections
import glob
import os

# The device plane of a TPU v5e trace has the lines "Scalar Unit", "XLA
# Modules", "XLA Ops", "Async XLA Ops" and "TC Overlay" (seen on the chip,
# PR 25). "XLA Ops" holds every operation the core ran, a `while` enclosing
# its body; the others restate it at another grain or show copies that
# overlap it. Where a plane has no such line, every line but these counts.
OP_LINE = "xla ops"
_NOT_OP_LINES = ("step", "xla modules", "xla traceme", "launch", "scalar unit",
                 "async xla ops", "tc overlay", "framework", "source",
                 "host offload", "sparsecore")
HARNESS_PREFIX = "bench/"


def union_intervals(intervals):
    """Merge (start, end) pairs; returns the sorted disjoint list."""
    merged = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip_intervals(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(intervals, lo=None, hi=None) -> float:
    """Seconds covered by at least one interval (ns in, seconds out)."""
    merged = union_intervals(intervals)
    if lo is not None:
        merged = clip_intervals(merged, lo, hi)
    return sum(e - s for s, e in merged) / 1e9


def self_times(events):
    """events: (name, start, end) on ONE line, where a parent (a `while`,
    a fusion's wrapper) encloses its children. Returns {name: self ns}:
    a parent's duration minus what its direct children cover."""
    out = collections.Counter()
    stack = []  # [name, end, child_ns, start]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][1]:
            n, end, child, start = stack.pop()
            out[n] += (end - start) - child
        if stack:
            stack[-1][2] += min(e, stack[-1][1]) - s
        stack.append([name, e, 0, s])
    while stack:
        n, end, child, start = stack.pop()
        out[n] += (end - start) - child
    return out


def idle_gaps(busy, lo, hi, spans):
    """Gaps of `busy` (disjoint, sorted) inside [lo, hi], each charged to
    the innermost harness span (name, start, end) that covers its middle.
    Returns {label: ns}."""
    out = collections.Counter()
    cursor = lo
    edges = clip_intervals(busy, lo, hi) + [(hi, hi)]
    for s, e in edges:
        if s > cursor:
            mid = (cursor + s) / 2.0
            cover = [sp for sp in spans if sp[1] <= mid < sp[2]]
            label = (min(cover, key=lambda sp: sp[2] - sp[1])[0]
                     if cover else "(no harness span)")
            out[label] += s - cursor
        cursor = max(cursor, e)
    return out


def _is_op_line(name: str) -> bool:
    low = name.lower()
    return not any(low.startswith(p) for p in _NOT_OP_LINES)


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_xplane(path: str, span_name: str = HARNESS_PREFIX + "traced"):
    """Read one xplane file. Returns a dict: busy_s (mean over device
    planes), window_s (the harness's traced span, else the extent of the
    device events), device_ops and idle_gaps (top ten each, seconds),
    planes and lines seen (so a reader can check what was counted)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host_spans, seen = [], [], {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU") \
            or plane.name.startswith("/device:GPU")
        lines = []
        has_op_line = any(ln.name.lower() == OP_LINE for ln in plane.lines)
        for line in plane.lines:
            if is_device:
                lines.append(line.name)
                if (line.name.lower() == OP_LINE if has_op_line
                        else _is_op_line(line.name)):
                    device.append((plane.name, line.name, [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]))
            else:
                for ev in line.events:
                    if ev.name.startswith(HARNESS_PREFIX):
                        host_spans.append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
        if is_device:
            seen[plane.name] = lines
    if not device:
        raise ValueError(f"no device plane in {path}: {list(seen) or 'none'}")
    traced = [sp for sp in host_spans if sp[0] == span_name]
    every = [(s, e) for _, _, evs in device for _, s, e in evs]
    if not every:
        raise ValueError(f"device planes hold no operation: {seen}")
    if traced:
        lo, hi = traced[0][1], traced[0][2]
    else:
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    by_plane = collections.defaultdict(list)
    ops = collections.Counter()
    for plane_name, _, evs in device:
        by_plane[plane_name].extend((s, e) for _, s, e in evs)
        ops.update(self_times(evs))
    busy = [busy_seconds(iv, lo, hi) for iv in by_plane.values()]
    first = union_intervals(next(iter(by_plane.values())))
    inner = [sp for sp in host_spans if sp[0] != span_name]
    gaps = idle_gaps(first, lo, hi, inner)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n[:120], ns / 1e9 / len(by_plane)]
                       for n, ns in ops.most_common(10)],
        "idle_gaps": [[n, ns / 1e9] for n, ns in gaps.most_common(10)],
        "lines": seen,
        "events": len(every),
    }
