"""GROUP BY (hash aggregate) with dual execution paths.

The third classic linearizing operator after join and sort: the linear path
builds a hash table of groups (spilling to grouped partitions under
work_mem), the tensor path segment-reduces along the key axis (the same
dimension-preserving structure as the fused join-aggregate).  Semantics are
identical; the executor treats it as another deferred decision point.

A group-by takes one key column or several.  Several keys group by their
lexicographic order: one multi-operand sort on the device, the dense rank
of each row's key tuple on the host.  A value is a stored column or a
named computed measure (an ``Expr`` over the rows), evaluated before the
reduction; integer sums are exact int64 sums.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from .linear_engine import _next_pow2, _splitmix64, table_bytes_estimate
from .metrics import OpMetrics, SpillAccount, Timer
from .relation import Relation
from .spill import SpillManager

__all__ = ["group_aggregate_linear", "group_aggregate_tensor",
           "group_aggregate_device", "Measure"]

_AGGS = ("sum", "count", "min", "max")

#: the key tuple's rank column inside a multi-key linear group-by
_GROUP_COL = "__group__"


class Measure:
    """A named computed measure: an ``Expr`` evaluated row-wise over the
    columns it reads.  Hashes and compares by its name and the expression's
    canonical token, so it can be a static argument of a compiled program
    (rebuilt but equal measures share one program)."""

    __slots__ = ("name", "expr", "_token")

    def __init__(self, name: str, expr):
        self.name = name
        self.expr = expr
        self._token = (name, expr.cache_token())

    def __hash__(self):
        return hash(self._token)

    def __eq__(self, other):
        return isinstance(other, Measure) and other._token == self._token

    def __call__(self, view):
        return self.expr(view)

    def columns(self):
        return self.expr.columns()


def _as_keys(key: Union[str, Sequence[str]]) -> Tuple[str, ...]:
    return (key,) if isinstance(key, str) else tuple(key)


def _value_reads(values: Mapping[str, str],
                 measures: Mapping[str, object]) -> List[str]:
    """The stored columns the values read, in first-use order."""
    out: List[str] = []
    for name in values:
        m = measures.get(name)
        for c in ([name] if m is None else sorted(m.columns())):
            if c not in out:
                out.append(c)
    return out


def _with_measures(rel: Relation, values: Mapping[str, str],
                   measures: Mapping[str, object]) -> Relation:
    """``rel`` with each measure that ``values`` names evaluated into a
    column of that name (numpy, row-wise)."""
    named = {n: m for n, m in measures.items() if n in values}
    if not named:
        return rel
    cols = dict(rel.columns)
    for n, m in named.items():
        cols[n] = np.broadcast_to(np.asarray(m(rel)), (len(rel),)).copy()
    return Relation(cols)


def _factorize_rows(cols: Sequence[np.ndarray]):
    """Dense lexicographic rank of each row's key tuple, and the distinct
    tuples in rank order (one array per key)."""
    n = len(cols[0])
    order = np.lexsort(tuple(reversed(cols)))
    sorted_cols = [np.asarray(c)[order] for c in cols]
    new = np.zeros(n, bool)
    if n:
        new[0] = True
        for c in sorted_cols:
            new[1:] |= c[1:] != c[:-1]
    rank = np.empty(n, np.int64)
    rank[order] = np.cumsum(new) - 1
    return rank, [c[new] for c in sorted_cols]


def _agg_inmem(rel: Relation, key: str, values: Dict[str, str]) -> Relation:
    keys = rel[key]
    uniq, inv = np.unique(keys, return_inverse=True)
    out: Dict[str, np.ndarray] = {key: uniq}
    for col, fn in values.items():
        v = rel[col]
        if fn == "sum":
            out[f"{fn}_{col}"] = np.bincount(inv, weights=v.astype(np.float64),
                                             minlength=len(uniq))
        elif fn == "count":
            out[f"{fn}_{col}"] = np.bincount(inv, minlength=len(uniq)).astype(np.float64)
        elif fn in ("min", "max"):
            fill = np.inf if fn == "min" else -np.inf
            acc = np.full(len(uniq), fill)
            ufunc = np.minimum if fn == "min" else np.maximum
            ufunc.at(acc, inv, v.astype(np.float64))
            out[f"{fn}_{col}"] = acc
        else:
            raise ValueError(fn)
    return Relation(out)


def _merge_groups(parts: List[Relation], key: str, values: Dict[str, str]) -> Relation:
    merged = parts[0]
    for p in parts[1:]:
        merged = merged.concat(p)
    keys = merged[key]
    uniq, inv = np.unique(keys, return_inverse=True)
    out = {key: uniq}
    for col, fn in values.items():
        name = f"{fn}_{col}"
        v = merged[name]
        if fn in ("sum", "count"):
            out[name] = np.bincount(inv, weights=v, minlength=len(uniq))
        else:
            fill = np.inf if fn == "min" else -np.inf
            acc = np.full(len(uniq), fill)
            (np.minimum if fn == "min" else np.maximum).at(acc, inv, v)
            out[name] = acc
    return Relation(out)


def _group_linear_one(rel: Relation, key: str, values: Dict[str, str],
                      work_mem: int, mgr: SpillManager = None
                      ) -> Tuple[Relation, OpMetrics]:
    """Hash aggregate on one key with work_mem discipline: when the group
    table would not fit, inputs hash-partition to disk and each partition
    aggregates independently (PostgreSQL's spill-to-disk hash
    aggregation)."""
    own = mgr is None
    mgr = mgr or SpillManager()
    spill = SpillAccount()
    peak = 0
    try:
        with Timer() as t:
            keys = rel[key].astype(np.int64)
            n_groups_est = min(len(rel), max(1, len(np.unique(
                keys[: min(len(keys), 65536)])) * max(1, len(keys) // 65536)))
            est = table_bytes_estimate(n_groups_est)
            if est <= work_mem or len(rel) <= 64:
                out = _agg_inmem(rel, key, values)
                peak = est
            else:
                fanout = min(64, max(2, _next_pow2(int(np.ceil(est / work_mem)))))
                spill.partition_passes += 1
                h = (_splitmix64(keys, salt=7) % np.uint64(fanout)).astype(np.int64)
                parts = []
                for f in range(fanout):
                    part = rel.take(np.nonzero(h == f)[0])
                    if len(part) == 0:
                        continue
                    path = mgr.write_relation(part, f"agg{f}", spill)
                    parts.append(path)
                peak = table_bytes_estimate(n_groups_est // fanout)
                results = []
                for path in parts:
                    part = mgr.read_relation(path, spill)
                    mgr.delete(path)
                    results.append(_agg_inmem(part, key, values))
                out = _merge_groups(results, key, values)
    finally:
        if own:
            mgr.cleanup()
    return out, OpMetrics(op="group_aggregate", path="linear",
                          rows_in=len(rel), rows_out=len(out),
                          wall_s=t.elapsed, spill=spill,
                          peak_working_set_bytes=peak)


def group_aggregate_linear(rel: Relation, key: Union[str, Sequence[str]],
                           values: Dict[str, str], work_mem: int,
                           mgr: SpillManager = None,
                           measures: Optional[Mapping[str, object]] = None
                           ) -> Tuple[Relation, OpMetrics]:
    """Host GROUP BY on one key or several (see the module docstring):
    several keys aggregate by the dense rank of the key tuple, then map
    each rank back to its keys."""
    keys = _as_keys(key)
    rel = _with_measures(rel, values, measures or {})
    if len(keys) == 1:
        return _group_linear_one(rel, keys[0], values, work_mem, mgr)
    rank, uniq = _factorize_rows([rel[k] for k in keys])
    inner = Relation({_GROUP_COL: rank, **{c: rel[c] for c in values}})
    out, m = _group_linear_one(inner, _GROUP_COL, values, work_mem, mgr)
    g = out[_GROUP_COL]
    cols = {k: u[g] for k, u in zip(keys, uniq)}
    cols.update((n, out[n]) for n in out.names if n != _GROUP_COL)
    return Relation(cols), m


def _group_reduce_impl(keys, valid, cols, fns, num_segments, use_kernel):
    """Device group-by core: factorize the key axes ON DEVICE (one
    lexicographic sort + run boundaries), then segment-reduce every
    aggregate column.  ``keys`` is a tuple of key arrays (several keys sort
    as one multi-operand sort); ``use_kernel``
    holds, per column, whether its sum/count takes the Pallas segment sum.

    ``valid`` masks physical rows that are not logical rows (the device-
    resident pipeline's capacity padding / filtered rows); masked rows carry
    zero weight and sink to the tail of the sorted key axis.  Output arrays
    are ``num_segments``-padded; the returned prefix mask selects the real
    groups.  Integer sums are exact int64 sums, returned as float64 like
    every other aggregate.  No host transfer happens anywhere in here.
    """
    import jax
    import jax.numpy as jnp

    from .tensor_engine import segment_sum_dispatch

    n = keys[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    # masked rows sink to the tail as the most significant sort key,
    # WITHOUT remapping their keys (a sentinel remap would collide with
    # real rows at the dtype extreme and merge segments)
    lead = [] if valid is None else [jnp.logical_not(valid).astype(jnp.int8)]
    ops = jax.lax.sort(tuple(lead) + tuple(keys) + (iota,), dimension=0,
                       is_stable=True, num_keys=len(lead) + len(keys))
    order = ops[-1]
    sks = ops[len(lead):-1]
    vmask = (jnp.ones((n,), bool) if valid is None
             else jnp.take(valid, order))
    boundary = jnp.zeros((n,), bool).at[0].set(True)
    for sk in sks:
        boundary = boundary.at[1:].set(boundary[1:] | (sk[1:] != sk[:-1]))
    # valid rows form a prefix, so within it `boundary` is exact
    newseg = boundary & vmask
    seg = jnp.cumsum(newseg.astype(jnp.int32)) - 1  # masked rows inherit ids; weight 0
    nseg = newseg.sum()
    # each group's key tuple, read at its first sorted row
    first = jax.ops.segment_min(jnp.where(vmask, iota, n - 1), seg,
                                num_segments=num_segments)
    first = jnp.clip(first, 0, n - 1)
    uniq = tuple(jnp.take(sk, first) for sk in sks)
    results = []
    for col, fn, kernel in zip(cols, fns, use_kernel):
        c = jnp.take(col, order)
        exact_int = (fn == "sum" and not kernel
                     and jnp.issubdtype(c.dtype, jnp.integer))
        if exact_int:
            r = jax.ops.segment_sum(
                jnp.where(vmask, c.astype(jnp.int64), 0), seg,
                num_segments=num_segments).astype(jnp.float64)
        elif fn == "sum":
            v = c.astype(jnp.float64)
            r = segment_sum_dispatch(jnp.where(vmask, v, 0.0), seg,
                                     num_segments, kernel)
        elif fn == "count":
            r = segment_sum_dispatch(vmask.astype(jnp.float64), seg,
                                     num_segments, kernel)
        elif fn == "min":
            r = jax.ops.segment_min(
                jnp.where(vmask, c.astype(jnp.float64), jnp.inf), seg,
                num_segments=num_segments)
        elif fn == "max":
            r = jax.ops.segment_max(
                jnp.where(vmask, c.astype(jnp.float64), -jnp.inf), seg,
                num_segments=num_segments)
        else:
            raise ValueError(fn)
        results.append(r)
    valid_out = jnp.arange(num_segments) < nseg
    return uniq, tuple(results), valid_out


def _group_program(keys, valid, inputs, spec, num_segments, use_kernel):
    """The compiled group-by: evaluate the measures, then reduce by the
    keys.  ``spec`` is static: per value ``(column, fn, Measure or
    None)``."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("measure"):
        cols = tuple(inputs[name] if m is None
                     else jnp.broadcast_to(jnp.asarray(m(inputs)),
                                           keys[0].shape)
                     for name, _fn, m in spec)
    return _group_reduce_impl(keys, valid, cols,
                              tuple(fn for _n, fn, _m in spec), num_segments,
                              use_kernel)


def group_aggregate_device(rel, key: Union[str, Sequence[str]],
                           values: Dict[str, str],
                           max_abs: Optional[Dict[str, Optional[int]]] = None,
                           measures: Optional[Mapping[str, object]] = None):
    """Device-resident GROUP BY: DeviceRelation → DeviceRelation, zero syncs.

    The seed's tensor group-by factorized keys on the host (np.unique) —
    a full device→host→device round trip per operator.  Here factorization
    is a device sort; the output stays device-resident with its real group
    count carried as a prefix validity mask.  ``max_abs`` gives, per value
    column, the largest |value| where the caller knows it (host data); the
    segment-sum kernel's exactness rule reads it.  ``key`` is one column or
    several; ``measures`` as for :func:`group_aggregate_linear`.
    """
    import jax.numpy as jnp

    from .device_relation import DeviceRelation
    from .tensor_engine import segment_sum_uses_kernel

    keys = _as_keys(key)
    measures = measures or {}
    spec = tuple((name, fn, Measure(name, measures[name])
                  if name in measures else None)
                 for name, fn in values.items())
    inputs = {c: rel.col(c) for c in _value_reads(values, measures)}
    key_decode = None
    if len(keys) == 1 and rel.columns[keys[0]].decode is not None:
        # packed key column: factorize in the CODE domain.  Both codecs are
        # order-preserving (FOR is value−min, dict codes are sorted-unique
        # ranks), so sorting codes sorts values and segment boundaries are
        # identical — only the per-group representative needs decoding, one
        # O(groups) device op after the reduce instead of an O(rows) decode
        # before it.
        key_col = rel.columns[keys[0]]
        keys_dev = (key_col.force_codes(),)
        key_decode = key_col.decode
    else:
        keys_dev = tuple(rel.col(k) for k in keys)
        if len(keys) == 1 and not jnp.issubdtype(keys_dev[0].dtype,
                                                  jnp.integer):
            # seed-compatible coercion: non-integer group keys truncate to
            # int64 (the segment machinery needs an integer coordinate axis)
            keys_dev = (keys_dev[0].astype(jnp.int64),)
    n = rel.num_physical_rows
    if n == 0:
        out_cols = {k: rel.col(k) for k in keys}
        for col, agg in values.items():
            out_cols[f"{agg}_{col}"] = jnp.zeros((0,), jnp.float64)
        return (DeviceRelation.from_arrays(out_cols),
                OpMetrics(op="group_aggregate", path="tensor", rows_in=0,
                          rows_out=0, wall_s=0.0, spill=SpillAccount()))
    # the segment-sum kernel's written rule, per column, before tracing:
    # counts add booleans; sums add values bounded by ``max_abs`` where the
    # caller knows the data, else by the column's dtype; a measure's sum
    # never takes it (its bound is not known before it is computed)
    max_abs = max_abs or {}
    use_kernel = tuple(
        segment_sum_uses_kernel(n, n, bool) if agg == "count"
        else segment_sum_uses_kernel(n, n, inputs[name].dtype,
                                     max_abs.get(name))
        if agg == "sum" and m is None else False
        for name, agg, m in spec)
    with Timer() as t:
        uniq, results, valid_out = _group_program_jit()(
            keys_dev, rel.valid, inputs, spec, n, use_kernel)
        if key_decode is not None:
            # decode-at-fetch for the group axis: garbage codes in invalid
            # segments decode to arbitrary (clipped) values, masked by the
            # valid_out prefix exactly like every other padded output
            uniq = (key_decode(uniq[0]),)
        out_cols = dict(zip(keys, uniq))
        for (col, agg), r in zip(values.items(), results):
            out_cols[f"{agg}_{col}"] = r
        out = DeviceRelation.from_arrays(out_cols, valid=valid_out)
    peak = n * 8 * (1 + len(keys) + len(values))
    return out, OpMetrics(op="group_aggregate", path="tensor",
                          rows_in=n, rows_out=n,
                          wall_s=t.elapsed, spill=SpillAccount(),
                          peak_working_set_bytes=peak, host_syncs=0)


_GROUP_PROGRAM_JIT = None


def _group_program_jit():
    """Lazy jit of the group program (spec/num_segments/use_kernel
    static)."""
    import jax

    global _GROUP_PROGRAM_JIT
    if _GROUP_PROGRAM_JIT is None:
        _GROUP_PROGRAM_JIT = jax.jit(
            jax.named_scope("op.group_by")(_group_program),
            static_argnames=("spec", "num_segments", "use_kernel"))
    return _GROUP_PROGRAM_JIT


def group_aggregate_tensor(rel: Relation, key: Union[str, Sequence[str]],
                           values: Dict[str, str], key_domain: int = None,
                           measures: Optional[Mapping[str, object]] = None
                           ) -> Tuple[Relation, OpMetrics]:
    """Dimension-preserving aggregate: segment reductions along the key axis
    (jit, static segment count) — no group hash table ever exists.

    Host-Relation API over :func:`group_aggregate_device`: lift, reduce on
    device, one batched fetch."""
    from .device_relation import DeviceRelation
    from .tensor_engine import host_max_abs

    dev = DeviceRelation.from_host(rel)
    bounds = {c: host_max_abs(np.asarray(rel[c])) for c in values
              if c in rel.names}
    with Timer() as t:
        out_dev, m = group_aggregate_device(dev, key, values, max_abs=bounds,
                                            measures=measures)
        syncs = 1
        if out_dev.valid is not None:
            # group outputs are padded to the physical row count; fetch the
            # group count (scalar sync) and device-slice so the batched
            # result fetch is O(groups), not O(rows)
            nseg = int(out_dev.valid.sum())
            syncs = 2
            out_dev = DeviceRelation.from_arrays(
                {k: out_dev.col(k)[:nseg] for k in out_dev.names})
        out = out_dev.to_host()
    peak = rel.nbytes() + len(out) * 8 * (len(_as_keys(key)) + len(values))
    return out, OpMetrics(op="group_aggregate", path="tensor",
                          rows_in=len(rel), rows_out=len(out),
                          wall_s=t.elapsed, spill=SpillAccount(),
                          peak_working_set_bytes=peak, host_syncs=syncs)
