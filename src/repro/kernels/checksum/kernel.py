"""Blocked lane checksum Pallas kernel ("lanesum32").

The paper's §7 integrity check re-reads data and computes checksums on
the DTN's CPUs.  On a TPU pod the *source-side* checksum of a checkpoint
shard can be computed on-device before D2H, removing the host hash from
the critical path.  Fletcher-style sequential checksums don't map to the
VPU, so we adapt: the data is the little-endian uint32 word stream
w_0, w_1, ... of the array's bytes (zero-padded tail), and

    a = sum w_j                 (mod 2^32, int32 wraparound)
    b = sum (j+1) * w_j         (index-weighted, order-sensitive)

The kernel never materialises that word stream.  It reads the array's
own elements, flat in (rows, 128) lanes, takes their bits, and puts
each element at its byte position of its word: element k of a 2-byte
dtype is the low (k even) or high (k odd) half of word k // 2, so it
adds ``u_k << 16 * (k % 2)`` to ``a`` and ``(k // 2 + 1)`` times that to
``b``.  Since a and b are sums, the result is the word-stream digest
bit for bit, and no narrow-minor-dimension intermediate (which the TPU
pads to 128 lanes) is ever built.  Each grid step folds a block into
per-lane (8, 128) int32 accumulators; a host fold of the lanes gives the
64-bit digest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS, LANES = 8, 128
BLOCK_ROWS = 1024  # rows of 128 elements per grid step (512 KiB of int32)
ROW_ALIGN = 32     # row tile of the narrowest (1-byte) element type
_INT = {1: jnp.int8, 2: jnp.int16, 4: jnp.int32}


def widen(x):
    """Elements of any 1-, 2- or 4-byte dtype -> their bits, zero-extended
    to int32."""
    itemsize = x.dtype.itemsize
    v = lax.bitcast_convert_type(x, _INT[itemsize]).astype(jnp.int32)
    return v if itemsize == 4 else v & ((1 << (8 * itemsize)) - 1)


def word_terms(v, row0, itemsize: int):
    """Per-element contributions to (a, b) for a (r, LANES) int32 block
    of zero-extended elements whose first row is global row ``row0``."""
    per = 4 // itemsize  # elements per 32-bit word
    rows = row0 + lax.broadcasted_iota(jnp.int32, v.shape, 0)
    lanes = lax.broadcasted_iota(jnp.int32, v.shape, 1)
    if per > 1:
        v = v << ((lanes & (per - 1)) * (8 * itemsize))
    word = rows * (LANES // per) + (lanes >> (per.bit_length() - 1))
    return v, v * (word + 1)  # int32 wraparound == mod 2^32


def _checksum_kernel(x_ref, a_ref, b_ref, *, n_rows: int, block_rows: int):
    ib = pl.program_id(0)

    @pl.when(ib == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        b_ref[...] = jnp.zeros_like(b_ref)

    row0 = ib * block_rows
    w, wi = word_terms(widen(x_ref[...]), row0, x_ref.dtype.itemsize)
    if n_rows % block_rows:  # ragged last block: rows past the end are 0
        valid = (row0 + lax.broadcasted_iota(jnp.int32, w.shape, 0)) < n_rows
        w = jnp.where(valid, w, 0)
        wi = jnp.where(valid, wi, 0)
    fold = lambda t: t.reshape(block_rows // ROWS, ROWS, LANES).sum(axis=0)
    a_ref[...] += fold(w)
    b_ref[...] += fold(wi)


def checksum_lanes(x, *, interpret: bool):
    """x: (n_rows, LANES) elements of a 1-, 2- or 4-byte dtype, n_rows a
    multiple of ROW_ALIGN -> (a_lanes, b_lanes), each (ROWS, LANES) int32."""
    n_rows = x.shape[0]
    block_rows = min(BLOCK_ROWS, n_rows)
    kernel = functools.partial(_checksum_kernel, n_rows=n_rows,
                               block_rows=block_rows)
    lanes = jax.ShapeDtypeStruct((ROWS, LANES), jnp.int32)
    acc = pl.BlockSpec((ROWS, LANES), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n_rows, block_rows),),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=[acc, acc],
        out_shape=[lanes, lanes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x)
