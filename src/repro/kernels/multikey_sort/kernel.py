"""Pallas TPU kernel: bitonic tile sort (stable via index tie-break).

The tensor-path sort (§IV.B) runs stable per-axis passes; this kernel was
written as their run-generation stage (the engine no longer dispatches it:
Mosaic cannot lower the 1-D partner gather, see ``ops.py``).  Each grid step
sorts one tile of (key, payload) pairs entirely in VMEM with a bitonic
network — log²(n)/2 vectorized compare-exchange sweeps, no HBM round trips.
Stability comes from tie-breaking on the payload when payloads are the
original indices (the composite (key, idx) is unique, making bitonic —
normally unstable — order-preserving).

Inter-tile merging stays in XLA (jnp) — the classic two-level sort: VMEM
bitonic runs + a merge pass, mirroring how the linear engine generates
work_mem-sized runs before its disk merge (but here runs are VMEM-sized and
the merge never leaves HBM).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bitonic_tile_sort_pallas"]


def _composite_gt(k_a, i_a, k_b, i_b):
    return (k_a > k_b) | ((k_a == k_b) & (i_a > i_b))


def _bitonic_kernel(key_ref, val_ref, okey_ref, oval_ref, *, n):
    keys = key_ref[...]
    vals = val_ref[...]
    idx = jax.lax.iota(jnp.int32, n)
    stages = int(math.log2(n))
    for k_exp in range(1, stages + 1):
        for j_exp in range(k_exp - 1, -1, -1):
            j = 1 << j_exp
            partner = idx ^ j
            pk = jnp.take(keys, partner)
            pv = jnp.take(vals, partner)
            is_lower = (idx & j) == 0
            asc = (idx & (1 << k_exp)) == 0
            lo_k = jnp.where(is_lower, keys, pk)
            lo_v = jnp.where(is_lower, vals, pv)
            hi_k = jnp.where(is_lower, pk, keys)
            hi_v = jnp.where(is_lower, pv, vals)
            swap = _composite_gt(lo_k, lo_v, hi_k, hi_v) == asc
            keys = jnp.where(swap, pk, keys)
            vals = jnp.where(swap, pv, vals)
    okey_ref[...] = keys
    oval_ref[...] = vals


def bitonic_tile_sort_pallas(keys, vals, *, tile: int = 1024,
                             interpret: bool = False):
    """keys/vals [N] (N % tile == 0, tile a power of 2).  Sorts each tile
    independently (ascending, stable when vals are unique indices)."""
    n = keys.shape[0]
    tile = min(tile, n)
    assert n % tile == 0 and tile & (tile - 1) == 0, (n, tile)
    kernel = functools.partial(_bitonic_kernel, n=tile)
    return pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(keys.shape, keys.dtype),
            jax.ShapeDtypeStruct(vals.shape, vals.dtype),
        ],
        interpret=interpret,
    )(keys, vals)
