"""Public kernel API: the one place that decides how a Pallas kernel runs.

Every op runs its Pallas kernel. Where the surrounding program is compiled
for a TPU the kernel is compiled by Mosaic; for any other platform (the CPU
tests) it runs in interpret mode. The choice follows the platform the
program is lowered for (``lax.platform_dependent``), not the host's default
backend, so an ahead-of-time compile for a TPU gets the compiled kernel even
on a host without one. The pure-jnp oracles in ``ref`` are test references
and never stand in for a kernel.
"""

from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention_pallas
from .mamba_scan import mamba_scan_pallas
from .prefix_scan import prefix_scan_pallas
from .psts_dispatch import dispatch_positions_pallas, dispatch_work_prefix_pallas

__all__ = ["prefix_scan", "dispatch_positions", "dispatch_work_prefix",
           "flash_attention", "mamba_scan"]


def _run(kernel, *args, **kw):
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False, **kw),
        default=functools.partial(kernel, interpret=True, **kw))


def prefix_scan(x, **kw):
    """Exclusive scan along the last axis."""
    return _run(prefix_scan_pallas, x, **kw)


def dispatch_positions(expert_idx, base, n_experts: int, **kw):
    return _run(dispatch_positions_pallas, expert_idx, base,
                n_experts=n_experts, **kw)


def dispatch_work_prefix(expert_idx, weights, n_experts: int, **kw):
    return _run(dispatch_work_prefix_pallas, expert_idx, weights,
                n_experts=n_experts, **kw)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    **kw):
    return _run(flash_attention_pallas, q, k, v, causal=causal,
                window=window, softcap=softcap, **kw)


def mamba_scan(da, dbx, **kw):
    return _run(mamba_scan_pallas, da, dbx, **kw)
