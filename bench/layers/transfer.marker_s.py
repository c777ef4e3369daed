"""Host seconds per GB moved in the restart-marker store (each load,
journal append, snapshot and clear, the wait for the store's lock
included), from the program's own ``marker`` spans inside the tasks.
Seconds of concurrent threads add up."""

from bench.program_spans import seconds


def read(run):
    moved = run.counters.get("bytes_moved")
    if not moved:
        return None
    s = seconds("marker", run.intervals.get("task"))
    return s / (moved / 1e9) if s is not None else None
