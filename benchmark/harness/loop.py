"""The closed loop: one client sends a request (the mix's ordered
queries, one after the other), and the next when the last has answered.
It stops sending once `seconds` have passed and lets the request in
flight answer; the window runs from the first send to that last
answer. Every request sent counts."""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional


def closed_loop(entry, queries: List[Dict], seconds: Optional[float],
                max_requests: Optional[int] = None,
                annotate=None, counters_each: bool = False) -> Dict:
    """`annotate(i)` gives a context manager put around request i (the
    profiler's annotation in a traced run). `counters_each` reads the
    engine's counters after every request (traced runs only: it costs
    a call between requests)."""
    requests: List[Dict] = []
    t_first = time.perf_counter()
    while True:
        now = time.perf_counter()
        if seconds is not None and requests and now - t_first >= seconds:
            break
        if max_requests is not None and len(requests) >= max_requests:
            break
        ctx = annotate(len(requests)) if annotate else contextlib.nullcontext()
        t_send = time.perf_counter()
        with ctx:
            recs = [entry.query(q) for q in queries]
        t_done = time.perf_counter()
        req = {"t_send": t_send, "t_done": t_done,
               "client_ms": (t_done - t_send) * 1e3, "queries": recs}
        if counters_each:
            req["counters_after"] = entry.counters()
        requests.append(req)
    return {"requests": requests, "t_first": t_first,
            "window_s": requests[-1]["t_done"] - t_first}
