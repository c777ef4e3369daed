"""Seconds per task from ``TransferManager.submit`` to the first line of
the worker that runs it (dispatch and the thread's start), from the
program's own ``queue-wait`` windows in host time."""

from bench.program_spans import seconds


def read(run):
    tasks = run.intervals.get("task")
    s = seconds("queue-wait", tasks)
    return s / len(tasks) if s is not None else None
