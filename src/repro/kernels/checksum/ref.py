"""numpy oracle for the lanesum32 checksum."""

from __future__ import annotations

import numpy as np

MOD = 1 << 32
_MASK = np.uint64(MOD - 1)

#: words folded per slice.  The uint64 working buffers stay at 512 KiB
#: whatever the length: full-length uint64 copies were faulted in afresh
#: on every call, at a cost that swung by about 1.7x with whether the
#: kernel backed them with huge pages.
CHUNK = 1 << 16


def lanesum32_ref(words) -> tuple[int, int]:
    """words: 1-D int32/uint32 array.  Returns (a, b) ints mod 2^32."""
    words = np.asarray(words).reshape(-1)
    n = words.size
    c = max(1, min(CHUNK, n))
    w = np.empty(c, np.uint64)
    idx = np.empty(c, np.uint64)
    prod = np.empty(c, np.uint64)
    ones = np.arange(1, c + 1, dtype=np.uint64)
    a = b = 0
    for s in range(0, n, c):
        m = min(c, n - s)
        ws, xs, ps = w[:m], idx[:m], prod[:m]
        np.copyto(ws, words[s:s + m], casting="unsafe")
        ws &= _MASK
        np.add(ones[:m], np.uint64(s), out=xs)
        if s + m > MOD - 1:
            xs &= _MASK
        # w, i < 2**32, so w * i < 2**64; the uint64 sum wraps mod 2**64,
        # a multiple of 2**32
        np.multiply(ws, xs, out=ps)
        a += int(ws.sum())
        b += int(ps.sum())
    return a % MOD, b % MOD


def digest_ref(data: bytes) -> str:
    """Byte-stream variant (little-endian words, zero-padded tail)."""
    pad = (-len(data)) % 4
    if pad:
        data = data + b"\0" * pad
    w = np.frombuffer(data, dtype="<u4")
    a, b = lanesum32_ref(w)
    return f"{b:08x}{a:08x}"
