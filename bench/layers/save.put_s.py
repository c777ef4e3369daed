"""Seconds per save in writes of objects and bundles to the store, from
the program's own ``ckpt.put`` spans."""

from bench.program_spans import seconds


def read(run):
    saves = run.intervals.get("save")
    s = seconds("ckpt.put", saves)
    return s / len(saves) if s is not None else None
