"""Fused scan-filter-aggregate Pallas kernels (TPC-H Q6 shape).

The Q6 hot loop is: 3 range predicates + masked sum of a product — pure
VPU work.  The engine's generic path runs it in emulated int64 (exact
decimals); this kernel keeps the inner loop in native int32 by splitting
each product into (hi, lo) 16-bit halves and accumulating both as int32
per block — exact, and sized so no 32-bit overflow is possible:

    product = price(int32, <= ~2^27 cents) * discount(int32, <= 10)
            <= ~2^31;  hi = product >> 16 <= 2^15, lo = product & 0xFFFF
    per-block sums over BLOCK_ROWS=8192 rows:
      sum(lo) <= 8192 * 65535 < 2^29   sum(hi) <= 8192 * 2^15 = 2^28

The final reduction over per-block partials runs in int64 outside the
kernel (tiny).  ≙ the reference's SIMD white-filter + sum fusion
(ob_pushdown_filter_simd.cpp + sum_simd.h) re-imagined for the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 8192           # 64 sublanes x 128 lanes
_SUB, _LANE = 64, 128


def _q6_kernel(ship_ref, disc_ref, qty_ref, price_ref, live_ref,
               hi_ref, lo_ref, *, ship_lo, ship_hi, disc_lo, disc_hi,
               qty_hi):
    ship = ship_ref[:]
    disc = disc_ref[:]
    qty = qty_ref[:]
    price = price_ref[:]
    live = live_ref[:]
    mask = ((ship >= ship_lo) & (ship < ship_hi)
            & (disc >= disc_lo) & (disc <= disc_hi)
            & (qty < qty_hi) & (live != 0))
    prod = price * disc * mask.astype(jnp.int32)
    hi = prod >> 16
    lo = prod & 0xFFFF
    # whole-array output block (Mosaic rejects (1,1) VMEM tiles); each
    # grid step owns one row of the partials array
    i = pl.program_id(0)
    # Reduce ONLY over sublanes in-kernel (axis 0), emitting one
    # 128-lane partial row per block; the final cross-lane reduction
    # runs outside the kernel in int64 XLA.  Two reasons, both Mosaic:
    # scalar-output reductions proxy through jnp.sum (which inserts an
    # int32->int64 convert under jax_enable_x64 that Mosaic won't
    # lower), and a lane-shaped store keeps the output VMEM-tileable.
    # reduce_sum_p is bound directly so the accumulator stays int32.
    # Bounds: sum over 64 sublanes of hi<=2^15 -> 2^21; lo<=0xFFFF ->
    # 2^22 — no int32 overflow.
    hsum = jax.lax.reduce_sum_p.bind(hi, axes=(0,))     # (128,)
    lsum = jax.lax.reduce_sum_p.bind(lo, axes=(0,))
    hi_ref[pl.dslice(i, 1), :] = hsum.reshape(1, _LANE)
    lo_ref[pl.dslice(i, 1), :] = lsum.reshape(1, _LANE)


@functools.partial(jax.jit, static_argnames=(
    "ship_lo", "ship_hi", "disc_lo", "disc_hi", "qty_hi", "interpret"))
def q6_filter_sum(shipdate, discount, quantity, extendedprice, live,
                  *, ship_lo, ship_hi, disc_lo, disc_hi, qty_hi,
                  interpret=False):
    """Exact fused Q6: sum(price * discount) over the filtered rows.

    Inputs are int32 column arrays (any length; padded internally) plus a
    live-row mask; returns the scale-4 fixed-point revenue as int64.
    """
    n = shipdate.shape[0]
    nblocks = max((n + BLOCK_ROWS - 1) // BLOCK_ROWS, 1)
    pad = nblocks * BLOCK_ROWS - n

    def prep(x, fill=0):
        x = x.astype(jnp.int32)
        if pad:
            x = jnp.concatenate(
                [x, jnp.full(pad, fill, dtype=jnp.int32)])
        return x.reshape(nblocks * _SUB, _LANE)

    ship = prep(shipdate)
    disc = prep(discount)
    qty = prep(quantity, fill=qty_hi)      # padded rows fail the filter
    price = prep(extendedprice)
    lv = prep(live.astype(jnp.int32))

    kernel = functools.partial(
        _q6_kernel, ship_lo=ship_lo, ship_hi=ship_hi,
        disc_lo=disc_lo, disc_hi=disc_hi, qty_hi=qty_hi)

    # The whole (chunk_blocks, 128) partials array stays VMEM-resident
    # for one pallas_call (the constant-index-map out spec), so bound it:
    # chunks of <= MAX_BLOCKS blocks (~1 MB of int32 partials) keep VMEM
    # flat no matter the input size; the int64 combine runs per chunk in
    # plain XLA.  (A (1,128) per-step out block would be ideal but Mosaic
    # requires the trailing block dims divisible by (8,128) or whole.)
    MAX_BLOCKS = 1024  # 8.4M rows per call
    # block indices are int32 on the chip: under jax_enable_x64 a Python 0
    # would trace as int64, which Mosaic does not legalize
    zero = np.int32(0)
    total = jnp.zeros((), jnp.int64)
    for s in range(0, nblocks, MAX_BLOCKS):
        nb = min(MAX_BLOCKS, nblocks - s)
        rows = slice(s * _SUB, (s + nb) * _SUB)
        blk = pl.BlockSpec((_SUB, _LANE), lambda i: (i, zero),
                           memory_space=pltpu.VMEM)
        out_blk = pl.BlockSpec((nb, _LANE), lambda i: (zero, zero),
                               memory_space=pltpu.VMEM)
        hi, lo = pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[blk, blk, blk, blk, blk],
            out_specs=(out_blk, out_blk),
            out_shape=(jax.ShapeDtypeStruct((nb, _LANE), jnp.int32),
                       jax.ShapeDtypeStruct((nb, _LANE), jnp.int32)),
            interpret=interpret,
        )(ship[rows], disc[rows], qty[rows], price[rows], lv[rows])
        total = total + (jnp.sum(hi.astype(jnp.int64)) << 16) + \
            jnp.sum(lo.astype(jnp.int64))
    return total
