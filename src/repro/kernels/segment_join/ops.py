"""Jit'd wrappers: segment sum, radix partition and hash probe kernels.

The raw Pallas kernels require row counts to be multiples of their tile
sizes (on the chip, multiples of 1024: XLA tiles a 1-D int32 array by
1024 and a block must match it); these wrappers pad arbitrary relation
sizes (segment id 0 with value 0 is sum-neutral; out-of-domain codes are
the partition/probe padding contract) so the core engine can hand them
real workloads.
The segment sum runs in float32 on the chip and in interpret mode alike.
``interpret`` defaults to False, the compiled TPU kernel; only an explicit
``interpret=True`` (the engine's ``REPRO_PALLAS=1`` lane off the chip, and
the parity tests) runs the interpreter.

:func:`radix_hash_probe` is the full radix-join probe: both sides are
radix-ordered by the top bits of their packed int32 codes (one
:func:`radix_partition` pass each), the domain-tiled hash table is built
and probed with per-tile block skipping, and the per-probe results are
gathered back to original row order.  The join cores in
``core/fused.py`` consume it through ``tensor_engine``'s ``use_pallas``
dispatch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import (join_table_build_pallas, join_table_probe_pallas,
                     radix_rank_pallas, segment_sum_pallas, segment_tile)

__all__ = ["segment_sum", "join_aggregate_kernel", "radix_partition",
           "radix_hash_probe"]


@partial(jax.jit, static_argnames=("num_segments", "tblk", "interpret"))
def segment_sum(seg_ids, values, num_segments: int, tblk: int = None,
                interpret: bool = False):
    """Per-segment f32 sums.  Exact only for integer-valued inputs whose
    every partial sum stays below 2**24 in magnitude — the caller's
    dispatch rule (``tensor_engine.segment_sum_dispatch``) enforces that."""
    n = seg_ids.shape[0]
    if n == 0:
        return jnp.zeros((num_segments,), jnp.float32)
    tblk = tblk or segment_tile(num_segments)
    vals = values.astype(jnp.float32)
    pad = (-n) % tblk
    seg = seg_ids.astype(jnp.int32)
    if pad:
        seg = jnp.concatenate([seg, jnp.zeros((pad,), jnp.int32)])
        vals = jnp.concatenate([vals, jnp.zeros((pad,), jnp.float32)])
    return segment_sum_pallas(seg, vals, num_segments,
                              tblk=tblk, interpret=interpret)


@partial(jax.jit, static_argnames=("num_segments", "interpret"))
def join_aggregate_kernel(build_keys, build_vals, probe_keys, probe_vals,
                          num_segments: int, interpret: bool = False):
    """Σ over (virtual) join pairs of b·p — join output never materialized."""
    sb = segment_sum(build_keys, build_vals, num_segments, interpret=interpret)
    sp = segment_sum(probe_keys, probe_vals, num_segments, interpret=interpret)
    cb = segment_sum(build_keys, jnp.ones_like(build_vals, jnp.float32),
                     num_segments, interpret=interpret)
    cp = segment_sum(probe_keys, jnp.ones_like(probe_vals, jnp.float32),
                     num_segments, interpret=interpret)
    return {"count": jnp.dot(cb, cp), "sum_prod": jnp.dot(sb, sp),
            "sum_add": jnp.dot(sb, cp) + jnp.dot(cb, sp)}


@partial(jax.jit, static_argnames=("num_buckets", "tblk", "interpret"))
def radix_partition(bucket_ids, num_buckets: int, tblk: int = 1024,
                    interpret: bool = False):
    """Stable partition positions: ``(dest, counts)`` where ``dest[i]`` is
    row ``i``'s position in partition-major order (rows of the same bucket
    keep their relative order) and ``counts`` is the bucket histogram.
    ``bucket_ids`` must lie in ``[0, num_buckets)``."""
    n = bucket_ids.shape[0]
    if n == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.zeros((num_buckets,), jnp.int32))
    b = bucket_ids.astype(jnp.int32)
    pad = (-n) % tblk
    if pad:
        # padded rows use bucket id == num_buckets: ranked 0, uncounted
        b = jnp.concatenate([b, jnp.full((pad,), num_buckets, jnp.int32)])
    rank, counts = radix_rank_pallas(b, num_buckets, tblk=tblk,
                                     interpret=interpret)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    dest = jnp.take(offsets, b[:n]) + rank[:n]
    return dest, counts


def _order(arr, dest, n):
    """Apply partition positions: ``out[dest[i]] = arr[i]``."""
    inv = jnp.zeros((n,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32))
    return jnp.take(arr, inv), inv


@partial(jax.jit, static_argnames=("domain", "tblk", "dblk", "interpret"))
def radix_hash_probe(bk, pk, domain: int, tblk: int = 1024, dblk: int = 1024,
                     interpret: bool = False):
    """Radix-partitioned hash-join probe in the packed code domain.

    ``bk``/``pk`` are int32 codes in ``[0, domain]`` — slot ``domain`` is
    the dead/padding slot by the dense-core convention (dead build and
    dead probe rows both land there; callers mask dead probes with their
    liveness predicate exactly as on the pure-jnp path).

    Returns ``(cnt_p, build_row, has_dup)``: per probe row the number of
    matching build rows and the largest matching build-row id (−1 on
    miss), plus whether any *live* slot holds more than one build row
    (the caller's retry-to-sorted-core signal).
    """
    nb, np_ = bk.shape[0], pk.shape[0]
    nblocks = -(-(domain + 1) // dblk)
    dpad = nblocks * dblk
    shift = max(1, dblk).bit_length() - 1          # log2(dblk), dblk pow2
    if nb == 0 or np_ == 0:
        cnt_p = jnp.zeros((np_,), jnp.int32)
        return cnt_p, cnt_p - 1, jnp.asarray(False)
    bk = bk.astype(jnp.int32)
    pk = pk.astype(jnp.int32)
    # 1. radix-order both sides by domain block (top code bits); codes
    # are non-negative so arithmetic >> equals a logical shift, and the
    # jnp operator keeps int32 under jax_enable_x64 (lax.shift_* would
    # reject the weakly-typed int64 shift operand)
    with jax.named_scope("pallas.partition.build"):
        bdest, _ = radix_partition(bk >> shift, nblocks, tblk=tblk,
                                   interpret=interpret)
        bk_ord, brow = _order(bk, bdest, nb)
    with jax.named_scope("pallas.partition.probe"):
        pdest, _ = radix_partition(pk >> shift, nblocks, tblk=tblk,
                                   interpret=interpret)
        pk_ord, _ = _order(pk, pdest, np_)
    # 2. build the domain-tiled table (pad rows use code dpad: no block)
    with jax.named_scope("pallas.table.build"):
        bpad = (-nb) % tblk
        if bpad:
            bk_ord = jnp.concatenate([bk_ord,
                                      jnp.full((bpad,), dpad, jnp.int32)])
            brow = jnp.concatenate([brow, jnp.zeros((bpad,), jnp.int32)])
        cnt_t, inv_t = join_table_build_pallas(bk_ord, brow, dpad,
                                               tblk=tblk, dblk=dblk,
                                               interpret=interpret)
    # 3. probe in radix order, then gather back to original row order
    with jax.named_scope("pallas.table.probe"):
        ppad = (-np_) % tblk
        if ppad:
            pk_ord = jnp.concatenate([pk_ord,
                                      jnp.full((ppad,), dpad, jnp.int32)])
        cnt_po, inv_po = join_table_probe_pallas(pk_ord, cnt_t, inv_t,
                                                 tblk=tblk, dblk=dblk,
                                                 interpret=interpret)
    with jax.named_scope("pallas.gather"):
        cnt_p = jnp.take(cnt_po, pdest)
        build_row = jnp.take(inv_po, pdest) - 1
        has_dup = jnp.max(cnt_t[:domain]) > 1
    return cnt_p, build_row, has_dup
