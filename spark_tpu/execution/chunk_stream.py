"""The host loop of an out-of-HBM scan, written once.

Every chunked scan (`streaming_agg.py` direct / spill / mesh,
`external.py` collect) runs the same host loop: open the source's
chunk stream behind the prefetcher, skip to a checkpoint cursor, take
the first chunk, then launch one chunk program per chunk under the
per-chunk retry and drain at the end. `drive` is that loop. What the
scans do NOT share — what is carried from chunk to chunk, the chunk
program, what happens at a chunk boundary — is a `Carry`, which each
scan's `begin` builds on the first chunk.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Optional

import jax

from ..columnar import bucket_capacity
from ..observability.spans import current_recorder, span
from .recovery import ChunkRetrier


def _nothing(*_args) -> None:
    pass


@dataclass
class Carry:
    """What one kind of chunked scan holds between chunks; the state
    itself lives in the closures. A replay of `fold` runs against the
    pre-chunk state: it advances the carry only as its last act, or
    leaves that to `took`, which runs once the chunk has succeeded."""

    fold: Callable  # (b, ci) -> out: the retried step, in chunk.launch
    took: Callable  # (out, b, ci): after success; True stops the stream
    #: (drain) -> the result. `drain` is the stream.drain span, not yet
    #: open: the carry opens it around the stream's one wait, so what
    #: it does before (flush) and after (merge a seed) stands outside
    finish: Callable
    check: Callable = _nothing  # (b): once a chunk, in chunk.launch
    #: (ci, b, t_in0, t_in1) at the chunk boundary, before the launch;
    #: t_in0..t_in1 is the consumer's wait for the chunk
    between: Callable = _nothing


def drive(leaf, chunk_rows: int, conf, recovery,
          begin: Callable[..., Optional[Carry]], empty: Callable = _nothing,
          *, start: int = 0):
    """Stream `leaf`'s chunks from cursor `start`: `begin(first,
    dictionaries)` finds or builds the chunk program on the first chunk
    and returns the Carry, or None to decline (`dictionaries()` gives the
    string dictionaries as grown so far); `empty()` answers a stream
    with no chunk left. Returns what the carry's `finish` makes of the
    `stream.drain` span it is handed; None when the stream is shorter
    than `start` or `begin` declines. The prefetch worker is joined on
    EVERY exit — exhaustion, an early stop, a decline, or an exception
    (fault, cancellation) unwinding mid-stream: no ingest daemon
    outlives its query."""
    from ..io.sources import maybe_prefetch
    # the prefetch worker's spans are caused by the span the stream
    # runs under (`streaming`), not by `stream.open`, a leaf beside them
    rec = current_recorder()
    cause = rec.current() if rec is not None else None
    chunks = None
    try:
        # up to the first chunk's taking: the retriers, the source's
        # chunk stream, the prefetcher, a checkpoint's skip
        with span("stream.open"):
            # chunk-granular retry (execution/recovery.py) on both
            # threads: the carry only advances after a chunk succeeds,
            # so a TRANSIENT fault replays exactly the failed chunk
            # against the pre-chunk state
            retrier, ingest = (ChunkRetrier(conf, recovery, site=site)
                               for site in ("stream_chunk",
                                            "ingest_prefetch"))
            chunks = maybe_prefetch(
                leaf.source.load_chunks(leaf.required_columns,
                                        leaf.pushed_filters, chunk_rows),
                conf, recovery, retry=ingest.run, cause=cause)
            if start and (not hasattr(chunks, "skip_chunks")
                          or chunks.skip_chunks(start) < start):
                return None  # stream shorter than the checkpoint cursor
        t_in0 = time.perf_counter()
        b = next(iter(chunks), None)
        t_in1 = time.perf_counter()
        if b is None:
            return empty()
        # the chunk program found or built on the first chunk
        with span("stream.begin"):
            sink = begin(b, lambda: dict(
                getattr(chunks, "dictionaries", None) or {}))
        if sink is None:
            return None
        ci = int(start)
        while b is not None:
            sink.between(ci, b, t_in0, t_in1)
            # the launch is an enqueue: it returns once the chunk's
            # program is dispatched, not when the device has run it
            with span("chunk.launch", chunk=ci):
                sink.check(b)
                out = retrier.run(lambda: sink.fold(b, ci), chunk=ci)
            if sink.took(out, b, ci):
                break
            ci += 1
            t_in0 = time.perf_counter()
            b = next(chunks, None)  # ingest un-retried: see ChunkRetrier
            t_in1 = time.perf_counter()
        # the stream's one wait: how far transfers and chunk programs
        # lag the host once the last chunk is launched, or the host's
        # merge of what it pulled
        return sink.finish(span("stream.drain"))
    finally:
        if hasattr(chunks, "close"):
            chunks.close()


def apply_join_overflow(flags, metrics, joins) -> bool:
    """Parse one chunk update's `join_overflow_`/`join_nonunique_`/
    `join_hashsat_` flag families and apply capacity growth /
    unique-build / hash-kernel fallbacks to `joins`. Returns True when
    anything changed."""
    overflow = [k for k, v in flags.items()
                if k.startswith(("join_overflow_", "join_nonunique_",
                                 "join_hashsat_"))
                and bool(v)]
    for k in overflow:
        _join, family, tag = k.split("_", 2)
        for j in joins:
            if j.tag != tag:
                continue
            if family == "nonunique":
                j.unique_build = False
            elif family == "hashsat":
                j.hash_fallback = False
            else:
                j.out_cap = bucket_capacity(
                    max(int(metrics[f"join_rows_{tag}"]), 8))
    return bool(overflow)


def run_through_joins(attempt, regrow, joins, driver: str):
    """One chunk through a program that streams through `joins`: the ONE
    copy of the chunked-join AQE protocol. `attempt()` runs the program
    -> (out, flags, metrics), one host sync; on an overflow the joins
    grow, `regrow()` re-jits under the grown `describe()` and the SAME
    chunk runs again against the pre-chunk state."""
    for _attempt in range(8):
        out, flags, metrics = attempt()
        flags, metrics = jax.device_get((flags, metrics))
        if not apply_join_overflow(flags, metrics, joins):
            return out
        regrow()
    raise RuntimeError(f"{driver}: streamed join capacity did not "
                       f"converge in 8 attempts")
