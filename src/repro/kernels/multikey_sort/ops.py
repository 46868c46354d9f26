"""Jit'd wrappers: tile sort + full multi-key sort (tile runs + XLA merge).

The engine does not dispatch here: the bitonic network gathers partners
with a 1-D ``jnp.take``, which Mosaic does not lower ("Only 2D gather is
supported"), so the kernel has never compiled for a TPU.  It runs in
interpret mode only, which is why ``interpret`` defaults to True, and the
engine's sorts take the jnp LSD passes (``tensor_engine.sort_perm_device``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import bitonic_tile_sort_pallas

__all__ = ["tile_sort", "multikey_sort_lsd", "multikey_sort_lsd_padded",
           "keys_fit_int32"]

_I32_MAX = 2**31 - 1


def keys_fit_int32(key_cols) -> bool:
    """Key columns the tile sorter can take without value loss: the kernel
    casts to int32, so unsigned 32-bit (which would wrap negative) needs
    headroom — only dtypes whose full range embeds in int32 qualify."""
    def ok(dt):
        if not jnp.issubdtype(dt, jnp.integer):
            return False
        info = jnp.iinfo(dt)
        return info.min >= -(2**31) and info.max < 2**31
    return all(ok(c.dtype) for c in key_cols)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@partial(jax.jit, static_argnames=("tile", "interpret"))
def tile_sort(keys, vals, tile: int = 1024, interpret: bool = True):
    return bitonic_tile_sort_pallas(keys.astype(jnp.int32),
                                    vals.astype(jnp.int32), tile=tile,
                                    interpret=interpret)


@partial(jax.jit, static_argnames=("tile", "interpret"))
def multikey_sort_lsd(key_cols, tile: int = 1024, interpret: bool = True):
    """Stable LSD multi-key sort (paper §IV.B) with the Pallas tile sorter as
    the inner stage.  key_cols: tuple of [N] int32 arrays, most-significant
    first.  Returns the permutation.  Requires N % tile == 0; the core engine
    calls :func:`multikey_sort_lsd_padded` for arbitrary N.

    Each LSD pass: bitonic tile runs (VMEM) + one jnp merge of the sorted
    runs (argsort over run-local ranks is XLA's efficient merge path)."""
    n = key_cols[0].shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    for col in key_cols[::-1]:
        keyed = col[perm]
        # stage 1: VMEM tile runs, payload = current perm position (stable)
        pos = jnp.arange(n, dtype=jnp.int32)
        k_sorted, v_sorted = tile_sort(keyed, pos, tile=tile,
                                       interpret=interpret)
        # stage 2: merge runs — stable argsort over tile-sorted keys is a
        # merge of pre-sorted runs for XLA's sort
        merge = jnp.argsort(k_sorted, stable=True)
        take = v_sorted[merge]
        perm = perm[take]
    return perm


@partial(jax.jit, static_argnames=("tile", "interpret"))
def multikey_sort_lsd_padded(key_cols, tile: int = 1024,
                             interpret: bool = True):
    """Arbitrary-N entry point for the kernel-path multi-key sort.

    Pads each LSD pass to a tile multiple with INT32_MAX sentinel keys.  The
    composite (key, position) tie-break makes every stage stable in the
    original position, so padded entries — whose positions exceed every real
    position — always land *after* real rows of equal key; dropping the tail
    of the merged order recovers the exact permutation of the real rows.

    Contract: key values must fit int32 and be < INT32_MAX (the sentinel);
    :func:`keys_fit_int32` states the dtype gate.
    """
    if not keys_fit_int32(key_cols):
        raise TypeError("tile-sort keys must embed in int32: "
                        f"{[str(c.dtype) for c in key_cols]}")
    n = key_cols[0].shape[0]
    if n == 0:
        return jnp.arange(0, dtype=jnp.int32)
    tile = min(tile, _next_pow2(n))
    n_pad = -(-n // tile) * tile
    perm = jnp.arange(n, dtype=jnp.int32)
    pad = jnp.full((n_pad - n,), _I32_MAX, jnp.int32)
    for col in key_cols[::-1]:
        keyed = jnp.concatenate([col.astype(jnp.int32)[perm], pad])
        pos = jnp.arange(n_pad, dtype=jnp.int32)
        k_sorted, v_sorted = tile_sort(keyed, pos, tile=tile,
                                       interpret=interpret)
        merge = jnp.argsort(k_sorted, stable=True)
        take = v_sorted[merge][:n]  # padded entries occupy the tail
        perm = perm[take]
    return perm
