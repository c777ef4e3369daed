"""Seconds per restore in ``jax.device_put`` of each leaf onto the
chip, from the program's own ``ckpt.place`` spans.  The call returns
once the copy is under way; the wait for the rest of it falls in the
restore's ``block_until_ready``, outside the span."""

from bench.program_spans import seconds


def read(run):
    restores = run.intervals.get("restore")
    s = seconds("ckpt.place", restores)
    return s / len(restores) if s is not None else None
