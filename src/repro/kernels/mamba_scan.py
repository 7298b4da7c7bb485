"""Pallas TPU kernel: blocked selective scan (Mamba-1 recurrence).

``h_t = da_t * h_{t-1} + dbx_t`` over time, carrying h in VMEM scratch across
sequential time blocks — the same blocked schedule as
``models.ssm.selective_scan_chunked``, with the state kept on-chip instead of
re-read from HBM per chunk.

Layout: (B, S, N, di) — di last so channel tiles are multiples of the 128
lane width (N is 16 for every assigned SSM arch and rides the sublane axis).
Grid = (B, di blocks, time blocks), time innermost/sequential; the in-block
recurrence is a log-depth doubling scan over the time axis in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba_scan_pallas"]


def _mamba_kernel(da_ref, dbx_ref, o_ref, h_ref, *, block_t):
    t_blk = pl.program_id(2)

    @pl.when(t_blk == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    da = da_ref[...].astype(jnp.float32)     # (bt, N, bd)
    dbx = dbx_ref[...].astype(jnp.float32)

    # log-depth in-block scan (Hillis-Steele over time, the paper's doubling
    # ladder): compose (a2*a1, a2*b1 + b2)
    a, bacc = da, dbx
    shift = 1
    while shift < block_t:
        a_prev = jnp.pad(a, ((shift, 0), (0, 0), (0, 0)),
                         constant_values=1.0)[:block_t]
        b_prev = jnp.pad(bacc, ((shift, 0), (0, 0), (0, 0)))[:block_t]
        bacc = a * b_prev + bacc
        a = a * a_prev
        shift *= 2
    # fold the carried state: h_t = bacc_t + (prod da up to t) * h_in
    h_in = h_ref[...]                        # (1, N, bd) -> broadcast
    h_all = bacc + a * h_in
    o_ref[...] = h_all.astype(o_ref.dtype)
    h_ref[...] = h_all[-1:]


@functools.partial(jax.jit, static_argnames=("block_t", "block_d",
                                             "interpret"))
def mamba_scan_pallas(da: jax.Array, dbx: jax.Array, *, block_t: int = 128,
                      block_d: int = 256, interpret: bool = False):
    """da, dbx: (B, S, N, di). Returns h: (B, S, N, di) float32."""
    b, s, n, di = da.shape
    block_t = min(block_t, s)
    block_d = min(block_d, di)
    pad_t = -s % block_t
    pad_d = -di % block_d
    if pad_t or pad_d:
        da = jnp.pad(da, ((0, 0), (0, pad_t), (0, 0), (0, pad_d)),
                     constant_values=1.0)
        dbx = jnp.pad(dbx, ((0, 0), (0, pad_t), (0, 0), (0, pad_d)))
    grid = (b, da.shape[3] // block_d, da.shape[1] // block_t)
    kernel = functools.partial(_mamba_kernel, block_t=block_t)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_t, n, block_d),
                         lambda bi, di_, ti: (bi, ti, 0, di_)),
            pl.BlockSpec((None, block_t, n, block_d),
                         lambda bi, di_, ti: (bi, ti, 0, di_)),
        ],
        out_specs=pl.BlockSpec((None, block_t, n, block_d),
                               lambda bi, di_, ti: (bi, ti, 0, di_)),
        out_shape=jax.ShapeDtypeStruct(da.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, n, block_d), jnp.float32)],
        interpret=interpret,
    )(da, dbx)
    return out[:, :s, :, :di]
