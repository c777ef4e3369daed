"""Seconds in the program's own spans, read from its host record.

The program stamps each span of its tracers in host time
(``repro.core.clock.host_now``, the ``perf_counter`` clock the harness
times its intervals on) and keeps them in one bounded ring per process,
read with ``repro.obs.host_spans``.  A program without that record, or
one whose ring dropped a span that ended inside the window, gives no
reading.
"""

from __future__ import annotations

from bench.trace import overlap, union


def seconds(name: str, intervals) -> float | None:
    """Seconds in the program's spans of ``name`` inside the union of
    ``intervals``: spans nested on one thread count once, concurrent
    threads add up.  None without intervals, without the host record,
    where the record dropped a span that ended after the window opened,
    or where no such span fell inside."""
    window = union(intervals or ())
    if not window:
        return None
    try:
        from repro.obs import host_spans
    except ImportError:
        return None
    record = host_spans(name)
    if not record.whole_since(window[0][0]):
        return None
    by_thread: dict[int, list] = {}
    for s in record.spans:
        by_thread.setdefault(s.thread, []).append((s.t0, s.t1))
    total = sum(overlap(window, a, b) for spans in by_thread.values()
                for a, b in union(spans))
    return total if total > 0 else None
