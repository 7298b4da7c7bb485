"""Pallas TPU kernel: fused PSTS dispatch position computation.

Computes, for a stream of routed tokens, each token's exclusive position
within its destination expert (the paper's per-node load scan ``S``) plus the
final per-expert fill counts — in one pass, without materialising the (T, E)
one-hot matrix in HBM (it lives blockwise in VMEM).

Grid = (token blocks,) iterated sequentially; the running fill count per
expert rides a VMEM scratch. Expert axis padded to the 128 lane width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .prefix_scan import block_scan

__all__ = ["dispatch_positions_pallas", "dispatch_work_prefix_pallas"]

_LANES = 128


def _dispatch_kernel(e_ref, base_ref, pos_ref, fill_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = base_ref[...].astype(jnp.int32)

    e = e_ref[...]                                   # (bt, 1) int32
    eids = jax.lax.broadcasted_iota(jnp.int32, (e.shape[0], _LANES), 1)
    onehot = (e == eids).astype(jnp.int32)           # (bt, E_pad) in VMEM
    cum = block_scan(onehot, 0) - onehot             # exclusive scan
    acc = acc_ref[...]                               # (1, E_pad)
    pos = ((cum + acc) * onehot).sum(axis=1, keepdims=True)
    pos_ref[...] = pos
    acc_ref[...] = acc + onehot.sum(axis=0, keepdims=True)
    fill_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("n_experts", "block_tokens", "interpret"))
def dispatch_positions_pallas(expert_idx: jax.Array, base: jax.Array, *,
                              n_experts: int, block_tokens: int = 256,
                              interpret: bool = False):
    """expert_idx: (T,) int32 destination per token; base: (E,) already
    filled. Returns (positions (T,), fill (E,)) — fill includes base."""
    t = expert_idx.shape[0]
    if n_experts > _LANES:
        raise NotImplementedError(
            f"expert axis > {_LANES} needs a second lane tile")
    block_tokens = min(block_tokens, t)
    pad_t = -t % block_tokens
    e = jnp.pad(expert_idx.astype(jnp.int32), (0, pad_t),
                constant_values=-1)[:, None]          # (Tp, 1)
    base_p = jnp.pad(base.astype(jnp.int32),
                     (0, _LANES - n_experts))[None, :]  # (1, E_pad)
    grid = (e.shape[0] // block_tokens,)
    pos, fill = pl.pallas_call(
        _dispatch_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_tokens, 1), lambda i: (i, 0)),
                  pl.BlockSpec((1, _LANES), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((block_tokens, 1), lambda i: (i, 0)),
                   pl.BlockSpec((1, _LANES), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((e.shape[0], 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, _LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, _LANES), jnp.int32)],
        interpret=interpret,
    )(e, base_p)
    return pos[:t, 0], fill[0, :n_experts]


def _work_prefix_kernel(e_ref, w_ref, pos_ref, fill_ref, acc_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    e = e_ref[0]                                     # (bt, 1) int32
    w = w_ref[0]                                     # (bt, 1)
    eids = jax.lax.broadcasted_iota(jnp.int32, (e.shape[0], _LANES), 1)
    onehot = (e == eids).astype(w.dtype)             # (bt, E_pad) in VMEM
    ww = onehot * w                                  # weight routed per lane
    cum = block_scan(ww, 0) - ww                     # exclusive weighted scan
    acc = acc_ref[...]                               # (1, E_pad)
    pos_ref[0] = ((cum + acc) * onehot).sum(axis=1, keepdims=True)
    acc_ref[...] = acc + ww.sum(axis=0, keepdims=True)
    fill_ref[0] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("n_experts", "block_tokens", "interpret"))
def dispatch_work_prefix_pallas(expert_idx: jax.Array, weights: jax.Array, *,
                                n_experts: int, block_tokens: int = 256,
                                interpret: bool = False):
    """Weighted variant of :func:`dispatch_positions_pallas`, batched over
    rows: ``expert_idx`` (R, T) int32 destination per token (-1 = none),
    ``weights`` (R, T) work units. Returns ``(prefix (R, T), fill (R, E))``
    where ``prefix[r, j]`` is the total weight of *earlier* same-destination
    tokens in row r — the FIFO backlog formed in front of token j by its own
    dispatch wave — and ``fill`` the per-expert routed totals. Grid =
    (rows, token blocks), token blocks innermost; the running per-expert
    weight rides a VMEM scratch reset at each row's first block."""
    r, t = expert_idx.shape
    if n_experts > _LANES:
        raise NotImplementedError(
            f"expert axis > {_LANES} needs a second lane tile")
    block_tokens = min(block_tokens, t)
    pad_t = -t % block_tokens
    e = jnp.pad(expert_idx.astype(jnp.int32), ((0, 0), (0, pad_t)),
                constant_values=-1)[:, :, None]       # (R, Tp, 1)
    w = jnp.pad(weights, ((0, 0), (0, pad_t)))[:, :, None]
    grid = (r, e.shape[1] // block_tokens)
    pos, fill = pl.pallas_call(
        _work_prefix_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_tokens, 1), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((1, block_tokens, 1), lambda i, j: (i, j, 0))],
        out_specs=[pl.BlockSpec((1, block_tokens, 1),
                                lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, 1, _LANES), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(e.shape, w.dtype),
                   jax.ShapeDtypeStruct((r, 1, _LANES), w.dtype)],
        scratch_shapes=[pltpu.VMEM((1, _LANES), w.dtype)],
        interpret=interpret,
    )(e, w)
    return pos[:, :t, 0], fill[:, 0, :n_experts]
