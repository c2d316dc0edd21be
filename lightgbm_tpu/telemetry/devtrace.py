"""From a profiler trace (`.xplane.pb`) to this program's layers.

`reduce_xplane(path)` returns device self seconds by `lgbm/` scope (and
`unscoped`, what carries no scope), the top device operations each with
the scope it was charged to, host self seconds by `lgbm/iter/*` span,
and every device idle gap charged to the innermost program span that
covers it. The pure functions below it take plain tuples, so a test can
feed them a hand-made event list.

Rules (those of `benchmarks/trace_reduce.py`, which the benchmark owns
and this module may not import):

- device time is read off the "XLA Ops" line of each `/device:TPU:*`
  plane; "XLA Modules", "Scalar Unit", "Async XLA Ops" and "TC Overlay"
  restate it at another grain or overlap it, and are left out;
- an operation's time is its SELF time: a `while` encloses its body's
  events on that line, so its duration minus what they cover;
- busy is the union of the line's intervals; scopes + `unscoped` sum to
  it, the events being properly nested.

Which stat carries the scope (seen by hand on the chip, TPU v5 lite, jax
0.9.0 / libtpu 0.0.34, PR 26): a device event's own stats are only
`device_offset_ps`, `device_duration_ps` and `Time Scale Multiplier`; the
HLO `op_name` path is the stat `tf_op` of the event's METADATA (beside
`hlo_category`, `flops`, `bytes_accessed`, `source`), which
`jax.profiler.ProfileData` does not hand out. So this module reads the
file's protobuf wire format itself (`read_xspace`: the handful of XSpace
fields it needs, no dependency). An event is charged to its OWN metadata,
so a fusion goes to the scope of the instruction XLA named it after, and
to the innermost scope where the path holds several
(`.../lgbm/hist/contract/while/body/lgbm/hist/gather/gather:` is a
gather).
"""
from __future__ import annotations

import collections
import glob
import os

from .layers import PREFIX, SCOPES, UNSCOPED

OP_LINE = "xla ops"
SCOPE_STAT = "tf_op"
NO_SPAN = "(no lgbm span)"
# XLA:TPU emits the histogram merge as a fusion of its own making
# (`calls=%all-reduce-scatter...`, emitter SingleInputAllReduceScatter
# Fusion) and gives it no `op_name`: the one collective of a grow program
# that large, every other (the best split's pmax and psums) keeps its
# path. An operation with no scope whose text names one of these is the
# merge's (compiled for a described v5e:2x2 and seen `unscoped` in the
# four-chip trace of PR 32, PERF.md section 5)
MERGE_SCOPE = "lgbm/hist/merge"
COLLECTIVES = ("all-reduce-scatter", "reduce-scatter", "all-reduce",
               "all-gather", "all-to-all")


def scope_of(path: str, op: str = "") -> str:
    """The innermost `SCOPES` name in an `op_name` path, the longest where
    two start at one place (`lgbm/gradients/rank_sort` over
    `lgbm/gradients`); without one, the merge's scope for an operation
    `op` whose text names a collective (`COLLECTIVES`), else `unscoped`."""
    best, at = UNSCOPED, -1
    for name in SCOPES:
        i = path.rfind(name)
        if i > at or (i == at >= 0 and len(name) > len(best)):
            best, at = name, i
    if at < 0 and any(word in op for word in COLLECTIVES):
        return MERGE_SCOPE
    return best


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


def self_times(events):
    """events: (key, start, end) on ONE line, parents enclosing children.
    Returns {key: self ns}: each event's duration minus what its direct
    children cover, summed by key."""
    out = collections.Counter()
    stack = []  # [key, start, end, child_ns]
    for key, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][2]:
            k, start, end, child = stack.pop()
            out[k] += (end - start) - child
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([key, s, e, 0])
    for k, start, end, child in stack:
        out[k] += (end - start) - child
    return out


def idle_gaps(busy, lo, hi, spans):
    """Gaps of `busy` (disjoint, sorted) inside [lo, hi], each charged to
    the innermost span (name, start, end) covering its middle. Returns
    {span name or NO_SPAN: ns}."""
    out = collections.Counter()
    cursor = lo
    for s, e in [iv for iv in busy if iv[1] > lo and iv[0] < hi] + [(hi, hi)]:
        s = max(s, lo)
        if s > cursor:
            mid = (cursor + s) / 2.0
            cover = [sp for sp in spans if sp[1] <= mid < sp[2]]
            out[min(cover, key=lambda sp: sp[2] - sp[1])[0]
                if cover else NO_SPAN] += s - cursor
        cursor = max(cursor, min(e, hi))
    return out


def reduce_events(device, host, top=10):
    """device: {plane: [(name, start, end, op_name path), ...]} from the
    operation line of each device plane; host: {thread: [(span name,
    start, end), ...]}, the program's own spans. Times in ns; seconds
    out, device numbers the mean over planes; `planes` keeps each
    plane's own busy seconds and scopes (the shards of a data-parallel
    job do unequal work)."""
    planes = max(len(device), 1)
    scopes, ops, op_scope = (collections.Counter(), collections.Counter(), {})
    every, busy_ns, by_plane = [], 0, {}
    for plane, events in device.items():
        keyed = [((name, scope_of(path, name)), s, e)
                 for name, s, e, path in events]
        own = collections.Counter()
        for (name, scope), ns in self_times(keyed).items():
            own[scope] += ns
            ops[name] += ns
            op_scope[name] = scope
        scopes.update(own)
        merged = union_intervals((s, e) for _, s, e, _ in events)
        busy_ns += sum(e - s for s, e in merged)
        every.append(merged)
        by_plane[plane] = {
            "busy_s": sum(e - s for s, e in merged) / 1e9,
            "scopes": {k: ns / 1e9 for k, ns in own.most_common()}}
    if not any(every):
        raise ValueError("the device planes hold no operation")
    lo = min(iv[0][0] for iv in every if iv)
    hi = max(iv[-1][1] for iv in every if iv)
    spans = [sp for thread in host.values() for sp in thread]
    host_self = collections.Counter()
    for thread in host.values():
        host_self.update(self_times(thread))
    gaps = idle_gaps(next(iv for iv in every if iv), lo, hi, spans)
    return {
        "busy_s": busy_ns / 1e9 / planes,
        "window_s": (hi - lo) / 1e9,
        "planes": by_plane,
        "scopes": {k: ns / 1e9 / planes for k, ns in scopes.most_common()},
        "ops": [[name[:120], ns / 1e9 / planes, op_scope[name]]
                for name, ns in ops.most_common(top)],
        "host_spans": {k: ns / 1e9 for k, ns in host_self.most_common()},
        "idle_gaps": {k: ns / 1e9 for k, ns in gaps.most_common()},
    }


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint
    or fixed field, a memoryview for a length-delimited one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _map_entry(buf):
    key = value = None
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def read_xspace(path: str):
    """The planes of an `.xplane.pb` as plain data: [{"name", "lines":
    [{"name", "events": [(event name, start ns, end ns, stats)]}]}],
    `stats` being the string stats of the event's metadata by name.
    Field numbers are those of tsl/profiler/protobuf/xplane.proto."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    planes = []
    for num, plane_buf in _fields(space):
        if num != 1:                          # XSpace.planes
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for num, v in _fields(plane_buf):
            if num == 2:                      # XPlane.name
                name = bytes(v).decode()
            elif num == 3:                    # XPlane.lines
                lines.append(v)
            elif num == 4:                    # XPlane.event_metadata
                key, value = _map_entry(v)
                event_meta[key] = value
            elif num == 5:                    # XPlane.stat_metadata
                key, value = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for n, x in _fields(value) if n == 2),
                    "")
        meta = {}
        for key, buf in event_meta.items():
            ev_name, stats = "", {}
            for num, v in _fields(buf):
                if num == 2:                  # XEventMetadata.name
                    ev_name = bytes(v).decode()
                elif num == 5:                # XEventMetadata.stats
                    stat = dict(_fields(v))
                    if 5 in stat:             # XStat.str_value
                        text = bytes(stat[5]).decode(errors="replace")
                    elif 7 in stat:           # XStat.ref_value
                        text = stat_names.get(stat[7], "")
                    else:
                        continue
                    stats[stat_names.get(stat.get(1), "")] = text
            meta[key] = (ev_name, stats)
        out_lines = []
        for line_buf in lines:
            line_name, t0, events = "", 0, []
            for num, v in _fields(line_buf):
                if num == 2:                  # XLine.name
                    line_name = bytes(v).decode()
                elif num == 3:                # XLine.timestamp_ns
                    t0 = v
                elif num == 4:                # XLine.events
                    events.append(v)
            parsed = []
            for ev in events:
                f = dict((n, x) for n, x in _fields(ev) if n in (1, 2, 3))
                ev_name, stats = meta.get(f.get(1), ("", {}))
                start = t0 + f.get(2, 0) / 1000.0     # offset_ps
                parsed.append((ev_name, start,
                               start + f.get(3, 0) / 1000.0, stats))
            out_lines.append({"name": line_name, "events": parsed})
        planes.append({"name": name, "lines": out_lines})
    return planes


def reduce_xplane(path: str, top: int = 10):
    """Read one xplane file and reduce it (see `reduce_events`); adds the
    device lines seen and the events counted."""
    device, host, seen = {}, {}, {}
    for plane in read_xspace(path):
        if plane["name"].startswith("/device:TPU"):
            seen[plane["name"]] = [ln["name"] for ln in plane["lines"]]
            for line in plane["lines"]:
                if line["name"].lower() == OP_LINE:
                    device[plane["name"]] = [
                        (name, s, e, stats.get(SCOPE_STAT, ""))
                        for name, s, e, stats in line["events"]]
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                spans = [(name, s, e) for name, s, e, _ in line["events"]
                         if name.startswith(PREFIX)]
                if spans:
                    host[f"{plane['name']}/{line['name']}"] = spans
    if not device:
        raise ValueError(f"no '{OP_LINE}' line on a TPU plane in {path}: "
                         f"{seen or 'no device plane'}")
    out = reduce_events(device, host, top)
    out["lines"] = seen
    out["events"] = sum(len(evs) for evs in device.values())
    return out


def host_spans(path: str) -> dict:
    """Seconds by name of the program's own host spans in one xplane file
    (summed over threads; a trace of set-up holds no device plane worth
    reducing, and `reduce_xplane` would refuse it)."""
    out = collections.Counter()
    for plane in read_xspace(path):
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for name, s, e, _ in line["events"]:
                    if name.startswith(PREFIX):
                        out[name] += (e - s) / 1e9
    return dict(out)


def layer_table(reduced) -> str:
    """The reduction as the markdown table PERF.md section 5 holds."""
    busy = reduced["busy_s"] or 1.0
    rows = ["| Layer (scope) | device self s | share of busy |",
            "| --- | --- | --- |"]
    rows += [f"| `{k}` | {v:.4f} | {100 * v / busy:.1f}% |"
             for k, v in reduced["scopes"].items()]
    planes = reduced.get("planes", {})
    if len(planes) > 1:
        names = sorted(planes)
        window = reduced["window_s"] or 1.0
        rows += ["", "| Scope, self s by device plane | "
                 + " | ".join(n.rsplit(":", 1)[-1] for n in names) + " |",
                 "| --- |" + " --- |" * len(names)]
        rows += [f"| `{k}` | " + " | ".join(
            f"{planes[n]['scopes'].get(k, 0.0):.4f}" for n in names) + " |"
            for k in reduced["scopes"]]
        rows += ["| busy s (idle share of the window) | " + " | ".join(
            f"{planes[n]['busy_s']:.4f} "
            f"({100 * (1 - planes[n]['busy_s'] / window):.2f}%)"
            for n in names) + " |"]
    rows += ["", "| Device operation | self s | scope |", "| --- | --- | --- |"]
    rows += [f"| `{n[:60]}` | {v:.4f} | `{sc}` |"
             for n, v, sc in reduced["ops"]]
    rows += ["", "| Host span | self s | idle gaps under it, s |",
             "| --- | --- | --- |"]
    names = list(reduced["host_spans"]) + [
        k for k in reduced["idle_gaps"] if k not in reduced["host_spans"]]
    rows += [f"| `{k}` | {reduced['host_spans'].get(k, 0.0):.4f} "
             f"| {reduced['idle_gaps'].get(k, 0.0):.4f} |" for k in names]
    return "\n".join(rows)
