"""Pallas TPU kernel: blocked exclusive prefix scan (the paper's core
operator, Definition 3.1).

Row-wise exclusive cumsum over the last axis. Grid = (row blocks, column
blocks); column blocks run innermost (TPU grids iterate the trailing axis
fastest and sequentially), carrying the running row totals in a VMEM scratch
— the classic reduce/downsweep carry pattern with the in-block scan on the
VPU as a log-depth doubling ladder (Mosaic has no cumsum lowering).

Block shape: (block_rows, block_cols) in VMEM; block_cols a multiple of 128
(lane width).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["prefix_scan_pallas", "block_scan"]


def _shift(x, s: int, axis: int):
    """``x`` moved ``s`` places toward higher indices along ``axis``; zeros
    enter at the low end."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (s, 0)
    return jax.lax.slice_in_dim(jnp.pad(x, pad), 0, x.shape[axis], axis=axis)


def block_scan(x, axis: int):
    """Inclusive scan of an in-VMEM block along ``axis``: a Hillis-Steele
    doubling ladder, log2(n) shifted adds."""
    shift = 1
    while shift < x.shape[axis]:
        x = x + _shift(x, shift, axis)
        shift *= 2
    return x


def _scan_kernel(x_ref, o_ref, carry_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...]                                  # (br, bc)
    inc = block_scan(x, 1) + carry_ref[...]         # carry: (br, 1)
    o_ref[...] = inc - x
    carry_ref[...] = inc[:, -1:]


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_cols", "interpret"))
def prefix_scan_pallas(x: jax.Array, *, block_rows: int = 8,
                       block_cols: int = 512,
                       interpret: bool = False) -> jax.Array:
    """Exclusive prefix sum along the last axis of ``x``: (rows, n)."""
    rows, n = x.shape
    block_rows = min(block_rows, rows)
    block_cols = min(block_cols, n)
    pad_r = -rows % block_rows
    pad_c = -n % block_cols
    xp = jnp.pad(x, ((0, pad_r), (0, pad_c))) if (pad_r or pad_c) else x
    grid = (xp.shape[0] // block_rows, xp.shape[1] // block_cols)
    out = pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, block_cols),
                               lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_rows, block_cols),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, xp.dtype),
        scratch_shapes=[pltpu.VMEM((block_rows, 1), xp.dtype)],
        interpret=interpret,
    )(xp)
    return out[:rows, :n]
