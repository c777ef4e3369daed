"""numpy oracle for the lanesum32 checksum."""

from __future__ import annotations

import numpy as np

MOD = 1 << 32


def lanesum32_ref(words) -> tuple[int, int]:
    """words: 1-D int32/uint32 array.  Returns (a, b) ints mod 2^32."""
    w = np.asarray(words).astype(np.uint64) & 0xFFFFFFFF
    idx = (np.arange(1, w.size + 1, dtype=np.uint64)) & 0xFFFFFFFF
    a = int(w.sum() % MOD)
    b = int((w * idx % MOD).sum() % MOD)
    return a, b


def digest_ref(data: bytes) -> str:
    """Byte-stream variant (little-endian words, zero-padded tail)."""
    pad = (-len(data)) % 4
    w = np.frombuffer(data + b"\0" * pad, dtype="<u4")
    a, b = lanesum32_ref(w)
    return f"{b:08x}{a:08x}"
