"""Seconds per save in the post-write re-reads of each verified object,
from the program's own ``ckpt.verify-read`` spans (the checksum of what
is read back is ``ckpt.digest``'s)."""

from bench.program_spans import seconds


def read(run):
    saves = run.intervals.get("save")
    s = seconds("ckpt.verify-read", saves)
    return s / len(saves) if s is not None else None
