"""Device-array checksums for checkpoint integrity (paper §7)."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .kernel import LANES, ROW_ALIGN, checksum_lanes, widen, word_terms

MOD = 1 << 32


def as_lanes(x) -> jnp.ndarray:
    """``x``'s elements flat in (rows, LANES), in their own dtype, with the
    tail zero-padded to a multiple of ROW_ALIGN rows.  At most one copy:
    the bit-cast to ints happens inside the kernel."""
    if x.dtype.itemsize not in (1, 2, 4):
        raise ValueError(f"lanesum32 on device takes 1-, 2- or 4-byte "
                         f"elements, not {x.dtype}")
    pad = (-x.size) % (ROW_ALIGN * LANES)
    if pad:
        x = jnp.pad(jnp.ravel(x), (0, pad))
    return x.reshape(-1, LANES)


@functools.partial(jax.jit, static_argnames="interpret")
def lanesum32_lanes(x, *, interpret: bool):
    """The word view and the Pallas kernel as one program."""
    return checksum_lanes(as_lanes(x), interpret=interpret)


@jax.jit
def _lanesum32_jnp(x):
    """jnp twin of the kernel, used when the Pallas path is off."""
    lanes = as_lanes(x)
    w, wi = word_terms(widen(lanes), 0, lanes.dtype.itemsize)
    return jnp.sum(w), jnp.sum(wi)


def _fold(v) -> int:
    return int(np.asarray(v, np.int64).astype(np.uint32)
               .astype(np.uint64).sum() % MOD)


def checksum_array(x, use_pallas: bool = True) -> tuple[int, int]:
    """Lanesum32 (a, b) of an on-device array's little-endian bytes.

    The kernel runs in interpret mode only for an array on the CPU
    backend; on an accelerator it runs compiled, with no fallback."""
    x = jnp.asarray(x)
    if x.size == 0:
        return 0, 0
    if not use_pallas:
        a, b = _lanesum32_jnp(x)
        return _fold(a), _fold(b)
    interpret = all(d.platform == "cpu" for d in x.devices())
    a_l, b_l = lanesum32_lanes(x, interpret=interpret)
    return _fold(a_l), _fold(b_l)


def checksum_digest(x, use_pallas: bool = True) -> str:
    a, b = checksum_array(x, use_pallas=use_pallas)
    return f"{b:08x}{a:08x}"
