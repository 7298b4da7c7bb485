"""Pallas TPU kernel: flash attention (online softmax), GQA, causal,
optional sliding window and logit soft-cap — the compute hot spot of 8/10
assigned architectures.

Layout: q (B, H, S, hd); k/v (B, KV, S, hd). Grid = (B*H, q blocks, kv
blocks), kv innermost/sequential; m/l/acc ride VMEM scratch and the output
block is finalised on the last kv step. Fully-masked kv blocks (beyond the
causal frontier or outside the sliding window) are skipped with ``pl.when``,
so window attention does proportionally less work — the structural win the
XLA fallback can't express.

Block sizes default to (128, 512): MXU-aligned (hd is 64..256 for all
assigned archs; the matmul contractions are multiples of 128 lanes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, block_q, block_k, causal, window, softcap,
                  seq_len):
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = jk * block_k

    # block-level skip: strictly above the causal diagonal, or entirely
    # left of the sliding window
    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
    if window is not None:
        run = run & (k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32)           # (bq, hd)
        k = k_ref[...].astype(jnp.float32)           # (bk, hd)
        v = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                logits.shape, 0)
        kj = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                logits.shape, 1)
        mask = kj < seq_len
        if causal:
            mask = mask & (qi >= kj)
        if window is not None:
            mask = mask & ((qi - kj) < window)
        logits = jnp.where(mask, logits, _NEG)

        m_prev = m_ref[...]                          # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(jk == nk - 1)
    def _finalise():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None,
                           softcap=None, block_q=128, block_k=512,
                           interpret=False):
    """q: (B, H, S, hd); k, v: (B, KV, S, hd) with H % KV == 0.
    Returns (B, H, S, hd)."""
    b, h, s, hd = q.shape
    kvh = k.shape[1]
    rep = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    pad_q = -s % block_q
    pad_k = -s % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    grid = (b * h, qp.shape[2] // block_q, kp.shape[2] // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=hd ** -0.5, block_q=block_q, block_k=block_k,
        causal=causal, window=window, softcap=softcap, seq_len=s)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bh, i, j: (bh // h, bh % h, i, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bh, i, j: (bh // h, (bh % h) // rep, j, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bh, i, j: (bh // h, (bh % h) // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda bh, i, j: (bh // h, bh % h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :s]
