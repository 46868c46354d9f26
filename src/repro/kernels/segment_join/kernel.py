"""Pallas TPU kernels: segment-sum, radix partition, hash-table probe.

Three VMEM-tiled kernels back the tensor engine's device joins and
aggregates, all built on the same idiom — data-dependent scatter/gather
expressed as one-hot masks reduced by matmuls or lane sums, which lowers
identically on TPU hardware and in interpret mode (the CPU fallback):

  * :func:`segment_sum_pallas` — per-tile one-hot matmul into a
    VMEM-resident ``[num_segments]`` accumulator (revisited across all
    tiles); the fused join-aggregate core streams rows exactly once.
  * :func:`radix_rank_pallas` — stable radix partitioning: one
    sequential pass computes each row's rank within its bucket plus the
    per-bucket histogram, using the revisited counts block as the
    running-offset accumulator.  The caller turns ranks into a
    partition-major permutation with one exclusive cumsum.
  * :func:`join_table_build_pallas` / :func:`join_table_probe_pallas` —
    the hash-join core in the packed int32 code domain.  The table
    (per-slot count + build-row id) is tiled over the code domain; both
    kernels run a 2-D grid over row tiles and domain blocks, ordered so
    that each output block is revisited on consecutive steps only, and
    *skip* blocks a tile cannot touch via ``pl.when`` on the tile's code
    min/max.
    Radix-ordering the inputs first (via :func:`radix_rank_pallas`)
    clusters each tile's codes into one or two domain blocks, so the
    quadratic grid degenerates to a near-linear sweep — that is the
    radix-join structure, with static shapes throughout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "segment_sum_pallas",
    "radix_rank_pallas",
    "join_table_build_pallas",
    "join_table_probe_pallas",
]


def _resident(t):
    """Index map of an accumulator block that every grid step revisits.
    Spelled from the traced step index: under the program's process-wide
    jax_enable_x64 a literal 0 would be an int64 constant, which Mosaic
    rejects."""
    return (t * 0,)


def _segsum_kernel(seg_ref, val_ref, out_ref, *, tblk, num_segments):
    # Every constant carries an explicit 32-bit dtype: under the program's
    # process-wide jax_enable_x64 a weakly typed Python scalar would put a
    # 64-bit value in the kernel body, which Mosaic cannot lower.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    seg = seg_ref[...]                      # [tblk] i32
    val = val_ref[...]                      # [tblk] f32
    onehot = (seg[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (tblk, num_segments), 1)).astype(jnp.float32)
    # HIGHEST keeps the f32 operands whole on the MXU: integer-valued sums
    # below 2**24 come out exact (the dispatch rule in tensor_engine only
    # sends such sums here)
    out_ref[...] += jax.lax.dot_general(
        val[None, :], onehot, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[0]


def segment_tile(num_segments: int) -> int:
    """Row tile for the segment-sum kernel: a multiple of 1024 (XLA's tile
    of a 1-D int32 array, which a block must match), and small enough that
    the [tile, num_segments] f32 one-hot stays within 16 MiB of VMEM at the
    4096-segment gate."""
    return 2048 if num_segments <= 1024 else 1024


def segment_sum_pallas(seg_ids, values, num_segments: int, *,
                       tblk: int = 2048, interpret: bool = False):
    """seg_ids [N] i32 (< num_segments), values [N] f32 → sums [num_segments]
    f32."""
    n = seg_ids.shape[0]
    tblk = min(tblk, n)
    assert n % tblk == 0, (n, tblk)
    kernel = functools.partial(_segsum_kernel, tblk=tblk,
                               num_segments=num_segments)
    return pl.pallas_call(
        kernel,
        grid=(n // tblk,),
        in_specs=[
            pl.BlockSpec((tblk,), lambda t: (t,)),
            pl.BlockSpec((tblk,), lambda t: (t,)),
        ],
        out_specs=pl.BlockSpec((num_segments,), _resident),
        out_shape=jax.ShapeDtypeStruct((num_segments,), jnp.float32),
        interpret=interpret,
    )(seg_ids, values.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Radix partition: stable bucket ranks + histogram in one sequential pass
# ---------------------------------------------------------------------------

def _radix_rank_kernel(bkt_ref, pos_ref, cnt_ref, *, tblk, num_buckets):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.int32)

    bkt = bkt_ref[...]                                     # [tblk] i32
    hit = bkt[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (tblk, num_buckets), 1)
    onehot = hit.astype(jnp.float32)
    # Exclusive running count of this tile's rows per bucket, as a strictly
    # lower-triangular one-hot matmul (Mosaic has no cumsum).  Operands are
    # 0/1 and every sum is at most tblk, so f32 on the MXU is exact.
    lower = (jax.lax.broadcasted_iota(jnp.int32, (tblk, tblk), 1)
             < jax.lax.broadcasted_iota(jnp.int32, (tblk, tblk), 0)
             ).astype(jnp.float32)
    excl = jax.lax.dot_general(lower, onehot, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    rank = jnp.sum(excl * onehot, axis=1,
                   dtype=jnp.float32).astype(jnp.int32)   # [tblk]
    # the revisited cnt block carries the running cross-tile base (TPU
    # grids execute sequentially)
    base = cnt_ref[...]                                    # [num_buckets]
    pos_ref[...] = jnp.sum(jnp.where(hit, base[None, :], jnp.int32(0)),
                           axis=1, dtype=jnp.int32) + rank
    cnt_ref[...] = base + jnp.sum(hit.astype(jnp.int32), axis=0,
                                  dtype=jnp.int32)


def radix_rank_pallas(bucket_ids, num_buckets: int, *, tblk: int = 1024,
                      interpret: bool = False):
    """bucket_ids [N] i32 → ``(rank, counts)``: each row's stable rank
    within its bucket and the per-bucket histogram.  Rows with bucket ids
    outside ``[0, num_buckets)`` are left out of the histogram and their
    ranks carry no meaning — that is the padding contract."""
    n = bucket_ids.shape[0]
    tblk = min(tblk, n)
    assert n % tblk == 0, (n, tblk)
    lanes = -(-num_buckets // 128) * 128   # one-hot width fills whole vregs
    kernel = functools.partial(_radix_rank_kernel, tblk=tblk,
                               num_buckets=lanes)
    rank, counts = pl.pallas_call(
        kernel,
        grid=(n // tblk,),
        in_specs=[pl.BlockSpec((tblk,), lambda t: (t,))],
        out_specs=[
            pl.BlockSpec((tblk,), lambda t: (t,)),
            pl.BlockSpec((lanes,), _resident),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((lanes,), jnp.int32),
        ],
        interpret=interpret,
    )(bucket_ids)
    return rank, counts[:num_buckets]


# ---------------------------------------------------------------------------
# Hash-join table build + probe, tiled over the packed code domain
# ---------------------------------------------------------------------------

def _table_build_kernel(bk_ref, brow_ref, cnt_ref, inv_ref, *, tblk, dblk):
    j = pl.program_id(0)    # domain block: the output block of this step
    i = pl.program_id(1)    # build row tile

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.int32)
        inv_ref[...] = jnp.zeros(inv_ref.shape, jnp.int32)

    codes = bk_ref[...]                                    # [tblk] i32
    lo = j * dblk
    # radix-ordered inputs cluster each tile into one or two domain
    # blocks; every other (block, tile) cell skips the one-hot entirely
    @pl.when((jnp.max(codes) >= lo) & (jnp.min(codes) < lo + dblk))
    def _accum():
        local = codes - lo
        onehot = (local[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (tblk, dblk), 1)).astype(jnp.int32)
        cnt_ref[...] += jnp.sum(onehot, axis=0, dtype=jnp.int32)
        rows = brow_ref[...]                               # [tblk] i32
        inv_ref[...] = jnp.maximum(
            inv_ref[...], jnp.max(onehot * (rows[:, None] + 1), axis=0))


def join_table_build_pallas(bk, brow, domain_pad: int, *, tblk: int = 1024,
                            dblk: int = 1024, interpret: bool = False):
    """Build the tiled hash table: ``(cnt, inv)`` over ``[domain_pad]``
    slots, where ``cnt[c]`` counts build rows with code ``c`` and
    ``inv[c]`` holds the largest matching ``brow + 1`` (0 = empty slot).
    Codes ≥ ``domain_pad`` are ignored (padding contract).

    The grid runs domain blocks outer and row tiles inner, so each output
    block is accumulated on consecutive steps only.  The compiled kernel
    writes an output block back to HBM when the next step's block differs
    and never reads it back: with row tiles outer, a block revisited after
    another block's steps started again from a stale buffer, and every
    build row accumulated before was lost."""
    n = bk.shape[0]
    tblk = min(tblk, n)
    assert n % tblk == 0 and domain_pad % dblk == 0, (n, tblk, domain_pad)
    kernel = functools.partial(_table_build_kernel, tblk=tblk, dblk=dblk)
    return pl.pallas_call(
        kernel,
        grid=(domain_pad // dblk, n // tblk),
        in_specs=[
            pl.BlockSpec((tblk,), lambda j, i: (i,)),
            pl.BlockSpec((tblk,), lambda j, i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((dblk,), lambda j, i: (j,)),
            pl.BlockSpec((dblk,), lambda j, i: (j,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((domain_pad,), jnp.int32),
            jax.ShapeDtypeStruct((domain_pad,), jnp.int32),
        ],
        interpret=interpret,
    )(bk, brow)


def _table_probe_kernel(pk_ref, cnt_ref, inv_ref, cntp_ref, invp_ref, *,
                        tblk, dblk):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cntp_ref[...] = jnp.zeros(cntp_ref.shape, jnp.int32)
        invp_ref[...] = jnp.zeros(invp_ref.shape, jnp.int32)

    codes = pk_ref[...]                                    # [tblk] i32
    lo = j * dblk

    @pl.when((jnp.max(codes) >= lo) & (jnp.min(codes) < lo + dblk))
    def _accum():
        hit = (codes - lo)[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (tblk, dblk), 1)
        # per-probe table gather as a masked lane reduction over the block
        # (exact int32 on the VPU; a probe's code lives in exactly one
        # block so += never double-adds)
        zero = jnp.int32(0)
        cntp_ref[...] += jnp.sum(jnp.where(hit, cnt_ref[...][None, :], zero),
                                 axis=1, dtype=jnp.int32)
        invp_ref[...] += jnp.sum(jnp.where(hit, inv_ref[...][None, :], zero),
                                 axis=1, dtype=jnp.int32)


def join_table_probe_pallas(pk, cnt, inv, *, tblk: int = 1024,
                            dblk: int = 1024, interpret: bool = False):
    """Probe the tiled hash table: per probe row, ``(cnt_p, inv_p)`` =
    (matches in the build side, largest build-row-id + 1 or 0).  Codes ≥
    ``len(cnt)`` gather nothing (padding contract)."""
    n = pk.shape[0]
    domain_pad = cnt.shape[0]
    tblk = min(tblk, n)
    assert n % tblk == 0 and domain_pad % dblk == 0, (n, tblk, domain_pad)
    kernel = functools.partial(_table_probe_kernel, tblk=tblk, dblk=dblk)
    return pl.pallas_call(
        kernel,
        grid=(n // tblk, domain_pad // dblk),
        in_specs=[
            pl.BlockSpec((tblk,), lambda i, j: (i,)),
            pl.BlockSpec((dblk,), lambda i, j: (j,)),
            pl.BlockSpec((dblk,), lambda i, j: (j,)),
        ],
        out_specs=[
            pl.BlockSpec((tblk,), lambda i, j: (i,)),
            pl.BlockSpec((tblk,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ],
        interpret=interpret,
    )(pk, cnt, inv)
