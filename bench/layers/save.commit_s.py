"""Seconds per save in the commit: the manifest's put, the rename of
the step's directory (which copies every object in the store) and the
``LATEST`` pointer, from the program's own ``ckpt.commit`` spans."""

from bench.program_spans import seconds


def read(run):
    saves = run.intervals.get("save")
    s = seconds("ckpt.commit", saves)
    return s / len(saves) if s is not None else None
