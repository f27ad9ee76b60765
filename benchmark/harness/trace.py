"""From the profiler's trace to numbers: the device's busy time, its
idle share, time by operation name, and the idle gaps.

`jax.profiler` writes `<dir>/plugins/profile/<time>/*.xplane.pb`;
`jax.profiler.ProfileData` reads it with nothing but JAX. A device
plane is `/device:TPU:<n>`; its `XLA Ops` line holds one event per
operation that ran (nested where a loop holds its body). Busy is the
union of those intervals, so nesting and overlap count once. The window
is the span of the harness's own `bench.request` annotations on the
host plane, which the profiler puts on the same clock.

`reduce_events` is the arithmetic on plain (start, duration, name)
tuples, so a small synthetic trace can test it.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[float, float, str]  # start_ns, duration_ns, name


def op_name(event_name: str) -> str:
    """The trace names an operation by its whole HLO instruction
    (`%fusion.7 = s32[...] fusion(...)`); its name is what stands before
    the `=`."""
    return event_name.split(" = ", 1)[0].lstrip("%")

REQUEST = "bench.request"
OPS_LINE = "XLA Ops"


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi) that no interval covers."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def leaves(events: List[Event]) -> List[Event]:
    """Events that hold no other event (a loop's body, not the loop)."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (s, d, n) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[0] >= s and nxt[0] + nxt[1] <= s + d \
                and (nxt[0], nxt[1]) != (s, d):
            continue  # the next event starts inside and ends inside
        out.append((s, d, n))
    return out


def reduce_events(per_device: List[List[Event]],
                  window: Optional[Tuple[float, float]]) -> Dict:
    """Busy seconds (mean over devices), idle share, seconds by
    operation name (leaf events, mean over devices) and the idle gaps
    of the first device, all inside `window` (ns; the span of the
    events when None)."""
    if not per_device or not any(per_device):
        return {}
    if window is None:
        starts = [e[0] for evs in per_device for e in evs]
        ends = [e[0] + e[1] for evs in per_device for e in evs]
        window = (min(starts), max(ends))
    lo, hi = window
    busy, by_name = [], {}
    clipped_all = []
    for evs in per_device:
        clipped = [(max(s, lo), min(s + d, hi), n) for s, d, n in evs
                   if s + d > lo and s < hi]
        clipped_all.append(clipped)
        busy.append(union_ns((a, b) for a, b, _ in clipped))
        for s, d, n in leaves([(a, b - a, n) for a, b, n in clipped]):
            by_name[n] = by_name.get(n, 0.0) + d / len(per_device)
    busy_s = sum(busy) / len(busy) / 1e9
    window_s = (hi - lo) / 1e9
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "ops_s": {n: v / 1e9 for n, v in by_name.items()},
            "gaps_ns": gaps(((a, b) for a, b, _ in clipped_all[0]), lo, hi),
            "window_ns": (lo, hi)}


def newest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise SystemExit(f"benchmark: the profiler wrote no trace under "
                         f"{log_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str, host_ops: bool = False):
    """(events per device, `bench.request` annotations) of a trace file.
    `host_ops` is for a rehearsal on the CPU, which has no device plane:
    it takes the host plane's events that carry an `hlo_op` instead. It
    is never set in a run that reports a number."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    per_device: List[List[Event]] = []
    requests: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    per_device.append([(float(e.start_ns),
                                        float(e.duration_ns),
                                        op_name(e.name))
                                       for e in line.events])
        elif plane.name.startswith("/host:CPU"):
            cpu_ops: List[Event] = []
            for line in plane.lines:
                for e in line.events:
                    if e.name == REQUEST:
                        requests.append((float(e.start_ns),
                                         float(e.duration_ns), e.name))
                    elif host_ops and e.duration_ns > 0 and any(
                            k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append((float(e.start_ns),
                                        float(e.duration_ns), e.name))
            if host_ops and cpu_ops:
                per_device.append(cpu_ops)
    return per_device, sorted(requests)


def reduce_file(path: str, host_ops: bool = False) -> Dict:
    per_device, requests = read_xplane(path, host_ops)
    window = None
    if requests:
        window = (requests[0][0], max(s + d for s, d, _ in requests))
    out = reduce_events(per_device, window)
    if out:
        out["requests_ns"] = [(s, s + d) for s, d, _ in requests]
    return out


def attribute_gaps(reduced: Dict, requests: List[Dict], top: int = 10):
    """The idle gaps by what the host was doing: each gap goes to the
    engine span that covers most of it (spans are on the host's
    `perf_counter`; request i's send is annotation i's start, which
    puts both on the trace's clock), else to `between requests`.
    Returns [[name, seconds], ...], the longest first."""
    spans = []  # (start_ns, end_ns, name) on the trace clock
    for (a_ns, _), req in zip(reduced.get("requests_ns", []), requests):
        spans.append((a_ns, a_ns + (req["t_done"] - req["t_send"]) * 1e9,
                      "request (outside engine spans)", 1))
        for q in req["queries"]:
            for s in q.get("spans") or []:
                spans.append((a_ns + (s["t0"] - req["t_send"]) * 1e9,
                              a_ns + (s["t1"] - req["t_send"]) * 1e9,
                              s["name"], 0))
    by_name: Dict[str, float] = {}
    for a, b in reduced.get("gaps_ns", []):
        # the shortest engine span that covers most of the gap, else
        # the one that covers the largest part of it
        covers = [(min(b, s1) - max(a, s0), s1 - s0, name, rank)
                  for s0, s1, name, rank in spans
                  if min(b, s1) > max(a, s0)]
        most = [c for c in covers if c[3] == 0 and c[0] >= 0.5 * (b - a)]
        if most:
            best = min(most, key=lambda c: c[1])[2]
        elif covers:
            best = max(covers, key=lambda c: (-c[3], c[0]))[2]
        else:
            best = "between requests"
        by_name[best] = by_name.get(best, 0.0) + (b - a) / 1e9
    return [[n, s] for n, s in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:top]]
