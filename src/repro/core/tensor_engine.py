"""The TENSOR execution path (the paper's contribution, §III–IV), in JAX.

Dimension preservation on TPU-class hardware means *static-shape, axis-
explicit* programs instead of pointer-chasing linearized intermediates:

  * ``tensor_join`` — equi-join as **sorted coordinate alignment**: the join
    key stays an explicit coordinate axis; build rows are ordered along it
    (``argsort``), probe coordinates are aligned with ``searchsorted`` and
    match ranges expanded by segment arithmetic into a *statically sized*
    index space (capacity + validity mask).  No hash table is materialized;
    memory traffic is deterministic O(N log N) — this is what keeps the path
    out of the spill-amplification regime (§VI: T_tensor(N) ≈ O(N)).

  * ``tensor_join_aggregate`` — the strongest form of delayed materialization:
    for join-then-aggregate queries the join output is **never produced**;
    both relations are segment-reduced along the shared key axis and the
    aggregate is a contraction (einsum) over that axis.

  * ``tensor_sort`` — multi-key sort performed *step-wise along key axes*
    (stable LSD passes), exactly §IV.B: the key combination is "not
    immediately reduced to linear comparison operations but sorted
    step-by-step within the multidimensional structure".

Device residency (this layer's contract): join capacity is computed *on
device* by the same sort+searchsorted the join itself uses — there is no
separate host planning sort — and the only device→host traffic a per-operator
call pays is one scalar match count plus one batched result fetch.  The
``*_device`` variants take and return :class:`DeviceRelation` and pay *zero*
syncs (or one scalar when a join must discover its capacity), deferring all
materialization to the query root.  Capacities are padded to powers of two so
repeated queries hit the jit compile cache instead of recompiling.

All entry points are jit-compiled with static capacities, so the compiled
program's working set is known at compile time — the tensor path cannot
"discover" at runtime that it must spill.
"""
from __future__ import annotations

import math
import os
from collections import Counter
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Relational payloads are 64-bit (SQL bigint); the tensor path must preserve
# them exactly.  Model code elsewhere in the framework always passes explicit
# dtypes, so enabling x64 here is safe for the LM substrate.
jax.config.update("jax_enable_x64", True)

from .device_relation import DeviceColumn, DeviceRelation
from .metrics import OpMetrics, SpillAccount, Timer
from .relation import Relation

__all__ = [
    "tensor_join",
    "tensor_join_aggregate",
    "tensor_sort",
    "tensor_join_device",
    "tensor_sort_device",
    "join_capacity",
    "aligned_join_indices",
    "capacity_bucket",
    "sort_perm_device",
    "use_pallas",
    "segment_sum_dispatch",
    "segment_sum_uses_kernel",
    "radix_hash_probe_dispatch",
    "kernels_traced",
]

# Distinct sentinels so masked-out build rows can never meet masked-out probe
# rows at the same key value.  Relations whose key domain includes these two
# extreme int64 values are not supported by the masked device join (documented
# contract; SQL bigint workloads never reach them).
_BUILD_DEAD_KEY = -(2**62) - 11
_PROBE_DEAD_KEY = -(2**62) - 22


def _next_pow2(n: int) -> int:
    return 1 << max(4, int(math.ceil(math.log2(max(1, n)))))


def capacity_bucket(n: int) -> int:
    """Power-of-two shape bucket: the static capacity handed to jit.

    Bucketing means nearby match counts land on the same compiled program —
    the compile cache is keyed on (capacity, dtypes, num_keys), not on the
    exact data-dependent count.
    """
    return _next_pow2(max(1, n))


# ---------------------------------------------------------------------------
# Pallas kernel dispatch
# ---------------------------------------------------------------------------

def use_pallas(num_segments: Optional[int] = None) -> bool:
    """Should the engine route segment/probe inner loops to Pallas kernels?

    ``REPRO_PALLAS=1`` forces the kernels on (interpret mode off-TPU),
    ``REPRO_PALLAS=0`` forces pure jnp, and the default ``auto`` uses the
    kernels on TPU backends only — interpret mode is a correctness fallback,
    not a fast path.  The one-hot kernels are additionally gated to
    modest segment counts / code domains (their VMEM tiles are
    [tile, num_segments]).
    """
    env = os.environ.get("REPRO_PALLAS", "auto")
    if env == "0":
        return False
    if num_segments is not None and num_segments > 4096:
        return False
    if env == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Interpret mode only off the chip: a TPU backend always runs the
    compiled kernel, whatever forced the dispatch."""
    return jax.default_backend() != "tpu"


#: every integer of magnitude below 2**24 is exact in float32
_F32_EXACT = 1 << 24

# one name per program a Pallas kernel is traced into (list.append is atomic,
# so concurrent serving threads tracing programs lose no entry)
_KERNELS_TRACED: list = []


def kernels_traced() -> Dict[str, int]:
    """How many programs each Pallas kernel has been traced into so far in
    this process (a kernel that never appears here never ran)."""
    return dict(Counter(_KERNELS_TRACED))


def segment_sum_uses_kernel(num_segments: int, n_rows: int, dtype,
                            max_abs: Optional[int] = None) -> bool:
    """The dispatch rule of the segment-sum kernel, decided before tracing.

    The kernel adds in float32, so it takes a sum only where the float32
    result is provably exact — the same number the jnp core gives:

      * :func:`use_pallas` enables kernels for ``num_segments``;
      * the values are integers or booleans (float columns never take it);
      * ``n_rows * bound < 2**24``, where ``bound`` is ``max_abs`` when the
        caller knows the data's largest |value|, else the dtype's range —
        then every partial sum is an integer below 2**24.
    """
    if not use_pallas(num_segments):
        return False
    dt = np.dtype(dtype)
    if dt == np.bool_:
        bound = 1
    elif dt.kind in "iu":
        info = np.iinfo(dt)
        bound = max(abs(int(info.min)), int(info.max))
        if max_abs is not None:
            bound = min(bound, int(max_abs))
    else:
        return False
    return n_rows * bound < _F32_EXACT


def segment_sum_dispatch(values: jnp.ndarray, seg_ids: jnp.ndarray,
                         num_segments: int, use_kernel: bool) -> jnp.ndarray:
    """Segment sum via the Pallas kernel when requested, else pure jnp.

    ``use_kernel`` comes from :func:`segment_sum_uses_kernel`, resolved by
    the caller *outside* any jit trace so the rule and the env-var toggle
    are honored per call, not frozen into a compiled program.
    """
    if use_kernel:
        from ..kernels.segment_join.ops import segment_sum as _pallas_segsum
        _KERNELS_TRACED.append("segment_sum")
        return _pallas_segsum(seg_ids, values, num_segments,
                              interpret=_interpret()).astype(values.dtype)
    return jax.ops.segment_sum(values, seg_ids, num_segments=num_segments)


def radix_hash_probe_dispatch(bk_codes, pk_codes, domain: int,
                              use_kernel: bool):
    """Dense-domain hash-probe core: Pallas radix join or pure-jnp scatter.

    Both paths share one contract (and are parity-tested bit-for-bit):
    codes lie in ``[0, domain]`` with slot ``domain`` as the dead/padding
    slot; the result is ``(cnt_p, build_row, has_dup)`` — per probe row
    the number of matching build rows and the largest matching build-row
    id (−1 on miss), plus whether any live slot collides (the caller's
    retry-to-sorted-core signal).  ``use_kernel`` is ``use_pallas(domain)``
    resolved outside jit traces: the kernel works in exact int32 over any
    code domain up to the 4096 gate.
    """
    if use_kernel:
        from ..kernels.segment_join.ops import radix_hash_probe
        _KERNELS_TRACED.append("radix_hash_probe")
        return radix_hash_probe(bk_codes.astype(jnp.int32),
                                pk_codes.astype(jnp.int32), domain,
                                interpret=_interpret())
    nb = bk_codes.shape[0]
    cnt = jnp.zeros((domain + 1,), jnp.int32).at[bk_codes].add(1)
    inv = jnp.zeros((domain + 1,), jnp.int32).at[bk_codes].max(
        jnp.arange(1, nb + 1, dtype=jnp.int32))
    cnt_p = jnp.take(cnt, pk_codes)
    build_row = jnp.take(inv, pk_codes) - 1
    has_dup = jnp.max(cnt[:domain]) > 1
    return cnt_p, build_row, has_dup


# ---------------------------------------------------------------------------
# Join: sorted coordinate alignment
# ---------------------------------------------------------------------------

@jax.named_scope("op.join")
def _join_plan_impl(build_keys, probe_keys):
    """Shared device planning stage: ONE sort + searchsorted produces both the
    exact match count (the capacity signal) and the alignment arrays the join
    expansion reuses — the seed's duplicate host-side planning sort is gone."""
    order = jnp.argsort(build_keys, stable=True)
    sorted_keys = jnp.take(build_keys, order)
    left = jnp.searchsorted(sorted_keys, probe_keys, side="left")
    right = jnp.searchsorted(sorted_keys, probe_keys, side="right")
    counts = right - left
    ends = jnp.cumsum(counts)
    starts = ends - counts
    if counts.shape[0]:
        total = ends[-1]
    else:
        total = jnp.asarray(0, ends.dtype)
    return order, left, starts, ends, total


_join_plan = jax.jit(_join_plan_impl)


@jax.named_scope("op.join")
def _expand_join_impl(order, left, starts, ends, capacity: int):
    n_build = order.shape[0]
    n_probe = ends.shape[0]
    slot = jnp.arange(capacity, dtype=ends.dtype)
    # which probe row does output slot s belong to?
    probe_idx = jnp.searchsorted(ends, slot, side="right")
    probe_idx_c = jnp.minimum(probe_idx, max(n_probe - 1, 0))
    offset = slot - starts[probe_idx_c]
    build_pos = left[probe_idx_c] + offset
    build_idx = jnp.take(order, jnp.clip(build_pos, 0, max(n_build - 1, 0)))
    total = ends[-1] if n_probe else jnp.asarray(0, ends.dtype)
    valid = slot < total
    return build_idx, probe_idx_c, valid


_expand_join = jax.jit(_expand_join_impl, static_argnames=("capacity",))


@partial(jax.jit, static_argnames=("capacity",))
def aligned_join_indices(
    build_keys: jnp.ndarray, probe_keys: jnp.ndarray, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Core dimension-preserving equi-join.

    Returns ``(build_idx, probe_idx, valid, total)`` where the first two are
    ``capacity``-sized gather indices into the original relations, ``valid``
    masks real matches, and ``total`` is the exact match count (callers can
    detect capacity overflow as ``total > capacity``).
    """
    order, left, starts, ends, total = _join_plan_impl(build_keys, probe_keys)
    build_idx, probe_idx, valid = _expand_join_impl(order, left, starts, ends,
                                                    capacity)
    return build_idx, probe_idx, valid, total


def join_capacity(build_keys, probe_keys) -> int:
    """Exact match count, computed ON DEVICE by the join's own planning stage.

    This models the "expected intermediate result size" signal the paper's
    execution-time selector observes (§III.C).  The seed ran a duplicate
    host-side O(N log N) sort here; now the one device sort is shared with
    the join itself and only the scalar count crosses to the host.
    """
    bk = jnp.asarray(build_keys)
    pk = jnp.asarray(probe_keys)
    if bk.shape[0] == 0 or pk.shape[0] == 0:
        return 0
    *_, total = _join_plan(bk, pk)
    return int(total)


def tensor_join(
    build: Relation,
    probe: Relation,
    key: str,
    capacity: Optional[int] = None,
) -> Tuple[Relation, OpMetrics]:
    """Tensor-path equi-join producing the same schema as the linear path.

    Host-Relation convenience API: internally runs the device-resident join
    and pays exactly two host syncs — the scalar match count (capacity
    discovery + overflow check) and one batched result fetch.  The seed paid
    a full host planning sort plus one transfer per payload column.
    """
    bk = np.asarray(build[key], dtype=np.int64)
    pk = np.asarray(probe[key], dtype=np.int64)
    if len(bk) == 0 or len(pk) == 0:
        out = {name: col[:0] for name, col in probe.columns.items()}
        out.update({f"b_{n}": c[:0] for n, c in build.columns.items() if n != key})
        return Relation(out), OpMetrics(
            op="hash_join", path="tensor", rows_in=len(build) + len(probe),
            rows_out=0, wall_s=0.0, spill=SpillAccount())
    with Timer() as t:
        order, left, starts, ends, total = _join_plan(jnp.asarray(bk),
                                                      jnp.asarray(pk))
        n = int(total)  # host sync #1: one scalar, no data
        if capacity is None:
            capacity = capacity_bucket(n)
        elif n > capacity:
            raise ValueError(f"capacity {capacity} < exact match count {n}")
        build_idx, probe_idx, _valid = _expand_join(order, left, starts, ends,
                                                    capacity)
        b_idx = build_idx[:n]
        p_idx = probe_idx[:n]
        # Late materialization: gather payload columns ON DEVICE, only valid
        # rows, then fetch everything in one batched transfer.
        out_dev: Dict[str, jnp.ndarray] = {}
        for name, col in probe.columns.items():
            out_dev[name] = jnp.take(jnp.asarray(col), p_idx)
        for name, col in build.columns.items():
            if name == key:
                continue
            out_dev[f"b_{name}"] = jnp.take(jnp.asarray(col), b_idx)
        if not out_dev:
            out_dev[key] = jnp.take(jnp.asarray(probe[key]), p_idx)
        fetched = jax.device_get(out_dev)  # host sync #2: the result
        result = Relation({k: np.asarray(v) for k, v in fetched.items()})
    peak = (
        bk.nbytes * 3  # keys + order + sorted copy
        + pk.nbytes * 3  # searchsorted operands
        + capacity * 8 * 3  # index space
    )
    metrics = OpMetrics(
        op="hash_join",
        path="tensor",
        rows_in=len(build) + len(probe),
        rows_out=len(result),
        wall_s=t.elapsed,
        spill=SpillAccount(),  # structurally zero: no spill regime exists
        peak_working_set_bytes=peak,
        host_syncs=2,
        # materializing host API: every input column crosses to the device
        # per call (the cached executor paths report 0 when warm)
        h2d_bytes=build.nbytes() + probe.nbytes(),
    )
    return result, metrics


def tensor_join_device(
    build: DeviceRelation,
    probe: DeviceRelation,
    key: str,
    capacity: Optional[int] = None,
) -> Tuple[DeviceRelation, OpMetrics]:
    """Device-resident equi-join: payload columns never move.

    The output :class:`DeviceRelation` carries *gather indices* into the
    input relations' base columns (late materialization) plus a validity
    mask over the capacity-padded index space.  Host traffic: one scalar
    match count when ``capacity`` must be discovered, otherwise zero.
    """
    if build.num_physical_rows == 0 or probe.num_physical_rows == 0:
        cols = {name: c.take_lazy(jnp.zeros((0,), jnp.int64))
                for name, c in probe.columns.items()}
        cols.update({f"b_{name}": c.take_lazy(jnp.zeros((0,), jnp.int64))
                     for name, c in build.columns.items() if name != key})
        if not cols:
            cols[key] = probe.columns[key].take_lazy(jnp.zeros((0,), jnp.int64))
        return DeviceRelation(cols), OpMetrics(
            op="hash_join", path="tensor",
            rows_in=len(build) + len(probe), rows_out=0, wall_s=0.0,
            spill=SpillAccount())
    bk = build.col(key).astype(jnp.int64)
    pk = probe.col(key).astype(jnp.int64)
    # masked-out input rows must never match: move them to dead key values
    if build.valid is not None:
        bk = jnp.where(build.valid, bk, _BUILD_DEAD_KEY)
    if probe.valid is not None:
        pk = jnp.where(probe.valid, pk, _PROBE_DEAD_KEY)
    with Timer() as t:
        order, left, starts, ends, total = _join_plan(bk, pk)
        # scalar sync: the capacity / overflow signal.  Even with an explicit
        # capacity the count must be verified — silently truncating the join
        # would corrupt results (the fused pipeline instead piggybacks this
        # check on its single result fetch).
        n = int(total)
        syncs = 1
        if capacity is None:
            capacity = capacity_bucket(n)
        elif n > capacity:
            raise ValueError(f"capacity {capacity} < exact match count {n}")
        build_idx, probe_idx, valid = _expand_join(order, left, starts, ends,
                                                   capacity)
        cols: Dict[str, DeviceColumn] = {}
        for name, c in probe.columns.items():
            cols[name] = c.take_lazy(probe_idx)
        for name, c in build.columns.items():
            if name == key:
                continue
            cols[f"b_{name}"] = c.take_lazy(build_idx)
        if not cols:
            cols[key] = probe.columns[key].take_lazy(probe_idx)
        out = DeviceRelation(cols, valid=valid)
    metrics = OpMetrics(
        op="hash_join",
        path="tensor",
        rows_in=len(build) + len(probe),
        rows_out=capacity,  # physical (padded) rows; logical count is masked
        wall_s=t.elapsed,
        spill=SpillAccount(),
        peak_working_set_bytes=bk.nbytes * 3 + pk.nbytes * 3 + capacity * 8 * 3,
        host_syncs=syncs,
    )
    return out, metrics


# ---------------------------------------------------------------------------
# Fused join + aggregate (join output never materialized)
# ---------------------------------------------------------------------------

# Both relations' values are contracted at ONE explicit dtype.  With x64
# enabled (module policy above) that is float64; the seed promoted build
# values to f64 while always truncating probe values to f32, which made
# Σ(b·p) silently lose probe precision.
_AGG_DTYPE = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


@partial(jax.jit, static_argnames=("num_segments", "use_kernel"))
@jax.named_scope("op.join_aggregate")
def _join_aggregate(
    build_keys, build_vals, probe_keys, probe_vals, num_segments: int,
    use_kernel: Tuple[bool, bool, bool, bool] = (False,) * 4
):
    """``use_kernel`` holds the segment-sum rule's answer for (build values,
    build counts, probe values, probe counts)."""
    kv_b, kc_b, kv_p, kc_p = use_kernel
    seg_b = segment_sum_dispatch(build_vals, build_keys, num_segments, kv_b)
    cnt_b = segment_sum_dispatch(
        jnp.ones_like(build_vals), build_keys, num_segments, kc_b)
    seg_p = segment_sum_dispatch(probe_vals, probe_keys, num_segments, kv_p)
    cnt_p = segment_sum_dispatch(
        jnp.ones_like(probe_vals), probe_keys, num_segments, kc_p)
    # SUM over join pairs of (b_val + p_val) decomposes along the key axis:
    #   sum_k [ cnt_p[k]*seg_b[k] + cnt_b[k]*seg_p[k] ]
    # and SUM of products contracts directly:  sum_k seg_b[k]*seg_p[k].
    sum_pairs = jnp.dot(cnt_b, cnt_p)
    sum_add = jnp.dot(seg_b, cnt_p) + jnp.dot(cnt_b, seg_p)
    sum_prod = jnp.dot(seg_b, seg_p)
    return sum_pairs, sum_add, sum_prod


def host_max_abs(col: np.ndarray) -> Optional[int]:
    """Exact max |value| of a host integer column (None when empty)."""
    if len(col) == 0 or col.dtype.kind not in "iub":
        return None
    return max(abs(int(col.min())), abs(int(col.max())))


def tensor_join_aggregate(
    build: Relation,
    probe: Relation,
    key: str,
    build_val: str,
    probe_val: str,
    key_domain: int,
) -> Tuple[dict, OpMetrics]:
    """SUM-style aggregates over the join result WITHOUT materializing it.

    Returns {count, sum_add, sum_prod} == aggregates over the (virtual) join
    of ``build ⋈ probe``: pair count, Σ(b+p), Σ(b·p).  Both value columns are
    contracted at one explicit dtype (:data:`_AGG_DTYPE`).
    """
    def rule(col: np.ndarray, counts: bool) -> bool:
        if counts:
            return segment_sum_uses_kernel(key_domain, len(col), np.bool_)
        return segment_sum_uses_kernel(key_domain, len(col), col.dtype,
                                       host_max_abs(col))

    bv, pv = np.asarray(build[build_val]), np.asarray(probe[probe_val])
    kernels = (rule(bv, False), rule(bv, True), rule(pv, False),
               rule(pv, True))
    with Timer() as t:
        pairs, s_add, s_prod = _join_aggregate(
            jnp.asarray(build[key], jnp.int32),
            jnp.asarray(build[build_val], _AGG_DTYPE),
            jnp.asarray(probe[key], jnp.int32),
            jnp.asarray(probe[probe_val], _AGG_DTYPE),
            key_domain,
            use_kernel=kernels,
        )
        pairs, s_add, s_prod = jax.device_get((pairs, s_add, s_prod))
        out = {
            "count": float(pairs),
            "sum_add": float(s_add),
            "sum_prod": float(s_prod),
        }
    metrics = OpMetrics(
        op="join_aggregate",
        path="tensor",
        rows_in=len(build) + len(probe),
        rows_out=1,
        wall_s=t.elapsed,
        spill=SpillAccount(),
        peak_working_set_bytes=key_domain * 4 * 4 + build.nbytes() + probe.nbytes(),
        host_syncs=1,
        h2d_bytes=(build[key].nbytes + build[build_val].nbytes
                   + probe[key].nbytes + probe[probe_val].nbytes),
    )
    return out, metrics


# ---------------------------------------------------------------------------
# Sort: step-wise multi-key (stable LSD passes over key axes)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_keys", "has_valid"))
@jax.named_scope("op.sort")
def _multikey_perm(key_cols: Tuple[jnp.ndarray, ...], valid, num_keys: int,
                   has_valid: bool = False) -> jnp.ndarray:
    n = key_cols[0].shape[0]
    perm = jnp.arange(n)
    # least-significant key first; stability makes the composition lexicographic
    for i in range(num_keys - 1, -1, -1):
        idx = jnp.argsort(key_cols[i][perm], stable=True)
        perm = perm[idx]
    if has_valid:
        # one extra stable LSD pass on validity: masked rows sink to the tail
        # without disturbing key order among live rows
        idx = jnp.argsort(jnp.logical_not(valid)[perm], stable=True)
        perm = perm[idx]
    return perm


def sort_perm_device(key_cols: Tuple[jnp.ndarray, ...],
                     valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Sort permutation over key axes: stable LSD passes, one per key axis.
    Masked rows always sink to the tail."""
    return _multikey_perm(tuple(key_cols), valid, len(key_cols),
                          has_valid=valid is not None)


def tensor_sort(
    rel: Relation, keys: Sequence[str]
) -> Tuple[Relation, OpMetrics]:
    """Tensor-path multi-key sort: per-axis stable passes, no key packing.

    Host-Relation API: permutation *and* payload gathers run on device; one
    batched fetch brings the result back (the seed fetched the permutation
    and re-gathered every column on the host)."""
    key_cols = tuple(jnp.asarray(rel[k]) for k in keys)
    with Timer() as t:
        perm = sort_perm_device(key_cols)
        out_dev = {k: jnp.take(jnp.asarray(v), perm)
                   for k, v in rel.columns.items()}
        fetched = jax.device_get(out_dev)
        out = Relation({k: np.asarray(v) for k, v in fetched.items()})
    peak = rel.nbytes() + len(rel) * 8 * 2
    metrics = OpMetrics(
        op="sort",
        path="tensor",
        rows_in=len(rel),
        rows_out=len(out),
        wall_s=t.elapsed,
        spill=SpillAccount(),
        peak_working_set_bytes=peak,
        host_syncs=1,
        h2d_bytes=rel.nbytes(),
    )
    return out, metrics


def tensor_sort_device(
    rel: DeviceRelation, keys: Sequence[str]
) -> Tuple[DeviceRelation, OpMetrics]:
    """Device-resident multi-key sort: zero host syncs.

    Computes the permutation on device and composes it into the relation's
    pending gather indices — payload columns are not touched."""
    key_cols = tuple(rel.col(k) for k in keys)
    with Timer() as t:
        perm = sort_perm_device(key_cols, valid=rel.valid)
        out = rel.take_lazy(perm)
    peak = sum(c.dtype.itemsize for c in key_cols) * len(rel) + len(rel) * 8 * 2
    metrics = OpMetrics(
        op="sort",
        path="tensor",
        rows_in=len(rel),
        rows_out=len(rel),
        wall_s=t.elapsed,
        spill=SpillAccount(),
        peak_working_set_bytes=peak,
        host_syncs=0,
    )
    return out, metrics
