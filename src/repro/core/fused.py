"""Fused device-resident pipelines: Join→[Filter]→[Sort]→[Aggregate] as ONE
jitted program.

The seed executor lowered every intermediate to a host-numpy Relation between
operators — its own premature materialization.  This module compiles the
common pipeline fragment into a single XLA program that:

  * carries **gather indices** between the fused operators (late
    materialization): the join emits index arrays, the filter emits a mask,
    the sort permutes the indices — payload columns are gathered on device
    only at the moment a stage actually consumes them, and columns nobody
    consumes never move at all;
  * keeps every shape **static and bucketed**: input columns are padded to
    power-of-two buckets and join capacity is a power-of-two bucket, so
    repeated queries (even with drifting row counts) hit the compile cache
    instead of recompiling — cache keys are
    ``(fragment shape, capacity, input buckets, dtypes, num sort keys, agg)``;
  * performs **≤ 1 device→host transfer per query** on the happy path: the
    single batched fetch of the root result (plus the piggybacked exact match
    count).  If the optimistic capacity bucket overflows — detected from that
    same fetch, never from a separate sync — the driver re-runs at the exact
    bucket, which the cache then holds for every later query of that shape.

Host-side planning (capacity estimation from a key sample) reads only the
numpy inputs and costs no device traffic.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .codec_device import decode_device, dict_bucket
from .metrics import OpMetrics, SpillAccount, Timer
from .relation import Relation
from .table_cache import get_device_layouts, key_stats
from .tensor_engine import (capacity_bucket, radix_hash_probe_dispatch,
                            use_pallas)

__all__ = ["FusedSpec", "match_fragment", "run_fused", "sharded_supported",
           "pipeline_cache_info", "pipeline_cache_clear"]

_I64_MAX = np.iinfo(np.int64).max


# ---------------------------------------------------------------------------
# Fragment description + plan matching
# ---------------------------------------------------------------------------

import types

_VALUE_TYPES = (int, float, complex, bool, str, bytes, type(None),
                types.ModuleType)


def _value_safe(v) -> bool:
    """Is ``v`` safe to compare *by value* in a cache key?  Only immutable
    primitives (and module references, which act as namespaces) qualify —
    an object with the default identity hash can mutate underneath while
    its key stays equal, which would resurrect a stale traced program."""
    if isinstance(v, tuple):
        return all(_value_safe(x) for x in v)
    return isinstance(v, _VALUE_TYPES)


def _freeze(v):
    """Type-tagged value for a cache key.  Python equates ``1 == 1.0 ==
    True`` while the traced program bakes the concrete dtype in, so a
    captured value rebound across those types must be a different cache
    entry, not a dict-key collision resurrecting the stale program."""
    if isinstance(v, tuple):
        return ("tuple",) + tuple(_freeze(x) for x in v)
    return (type(v).__name__, v)


def _predicate_key(fn: Optional[Callable]):
    """Cache identity for a filter predicate.

    IR-built predicates (:class:`repro.core.expr.Expr`) carry their own
    canonical :meth:`~repro.core.expr.Expr.cache_token` — structural value
    identity with no bytecode inspection at all; this is the primary path
    for queries built through :mod:`repro.core.session`.

    Legacy lambdas fall back to bytecode keying: plans typically rebuild
    their predicate lambda per query; keying on ``id(fn)`` would miss the
    cache every time and pin each dead lambda alive inside a compiled
    program.  Identical code at the same source location with equal
    closure/default/global captures is the same predicate — but only when
    every captured value is value-comparable (:func:`_value_safe`), and
    captured values are *type-tagged* (:func:`_freeze`) so rebinding a
    cell across equal-comparing types (``1`` → ``1.0`` → ``True``) is a
    different entry.  Anything else (mutable objects, arrays, nested
    functions) falls back to object identity: fresh lambdas then re-trace
    (correct, just slower), and a *reused* lambda over mutated state keeps
    jax.jit's own closed-over-state semantics.
    """
    if fn is None:
        return None
    from .expr import CombinedPredicate, Expr

    if isinstance(fn, Expr):
        return ("expr", fn.cache_token())
    if isinstance(fn, CombinedPredicate):
        # planner-merged mixed conjunction: compose the per-part keys so a
        # replanned query (fresh wrapper, same parts) stays one cache entry
        return ("and",) + tuple(_predicate_key(p) for p in fn.parts)
    try:
        code = fn.__code__
        cells = tuple(c.cell_contents for c in (fn.__closure__ or ()))
        # referenced globals are baked into the traced program too — a
        # module-level THRESHOLD change must be a different cache entry
        globs = tuple((nm, fn.__globals__.get(nm)) for nm in code.co_names)
        defaults = fn.__defaults__ or ()
        if not (_value_safe(cells) and _value_safe(defaults)
                and all(_value_safe(v) for _, v in globs)):
            return ("id", id(fn))
        key = ("code", code.co_filename, code.co_firstlineno, code.co_code,
               code.co_consts, _freeze(cells),
               tuple((nm, _freeze(v)) for nm, v in globs), _freeze(defaults))
        hash(key)
        return key
    except Exception:
        return ("id", id(fn))


@dataclasses.dataclass(frozen=True)
class FusedSpec:
    """A fusable plan fragment over a Scan join: ``[Project](Aggregate?(
    Sort?(Filter?(Join))))``.  ``project`` narrows a relation root's output
    schema — projected-away columns are never gathered and never cross the
    device→host boundary."""

    join_key: str
    filter_fn: Optional[Callable]  # predicate over a column view, or None
    sort_keys: Tuple[str, ...]     # () = no sort stage
    agg: Optional[Tuple[str, str]]  # (column, fn) for a scalar root, or None
    project: Optional[Tuple[str, ...]] = None  # relation-root column subset
    # a computed measure (Expr over the joined columns) the scalar root
    # reduces in place of a stored column; ``agg[0]`` is then its name
    measure: Optional[object] = None

    def cache_signature(self) -> Tuple:
        return (self.join_key, _predicate_key(self.filter_fn),
                self.sort_keys, self.agg, self.project,
                None if self.measure is None else self.measure.cache_token())

    def agg_column(self, view):
        """The column the scalar root reduces: stored, or the measure
        evaluated over the view."""
        if self.measure is None:
            return view[self.agg[0]]
        with jax.named_scope("measure"):
            return jnp.asarray(self.measure(view))


def match_fragment(plan):
    """Recognize Aggregate?(Sort?(Filter?(Join(Scan, Scan)))) fragments.

    Returns ``(spec, build_relation, probe_relation)`` or None.  At least one
    of the Filter/Sort/Aggregate stages must be present (a bare join gains
    nothing from fusion over the device-resident per-op path; a filtered
    join does — the predicate folds into the validity mask, and the
    planner's pushed-down filters keep multi-join stages on this path).
    """
    from .executor import Aggregate, Filter, Join, Project, Scan, Sort

    node = plan
    agg = None
    measure = None
    sort_keys: Tuple[str, ...] = ()
    filter_fn = None
    project = None
    if isinstance(node, Project):
        project = tuple(node.columns)
        node = node.child
    if isinstance(node, Aggregate):
        if project is not None:
            return None  # Project(Aggregate) is not a planner shape
        agg = (node.column, node.fn)
        measure = node.measure
        node = node.child
    if isinstance(node, Sort):
        sort_keys = tuple(node.keys)
        node = node.child
    if isinstance(node, Filter):
        filter_fn = node.predicate
        node = node.child
    if not isinstance(node, Join):
        return None
    if not (isinstance(node.build, Scan) and isinstance(node.probe, Scan)):
        return None
    if agg is None and not sort_keys and filter_fn is None and project is None:
        return None
    build, probe = node.build.relation, node.probe.relation
    if len(build) == 0 or len(probe) == 0:
        return None  # degenerate inputs keep the generic path's exact semantics
    return (FusedSpec(node.key, filter_fn, sort_keys, agg, project,
                      measure), build, probe)


# ---------------------------------------------------------------------------
# Column view: late materialization inside the traced program
# ---------------------------------------------------------------------------

def _decoders(sigs, dicts, refs):
    """Per-column device decode closures from static layout signatures plus
    the runtime dictionary/reference-point inputs.  ``None`` marks a plain
    (raw-layout) column — no decode work is ever traced for it."""
    out = {}
    for name, (enc, _cdt, ldt) in sigs:
        if enc == "raw":
            out[name] = None
        elif enc == "for":
            out[name] = (lambda a, _l=ldt, _r=refs[name]:
                         decode_device(a, "for", _l, ref=_r))
        else:
            out[name] = (lambda a, _l=ldt, _d=dicts[name]:
                         decode_device(a, "dict", _l, dict_values=_d))
    return out


class _JoinView:
    """Column access over the joined index space; gathers on first touch only.

    Presents the joined schema (probe columns under their own names, build
    columns as ``b_<name>``, probe's key column under the join key).  Filter
    predicates receive this view — numpy-style expressions trace through it.

    ``probe_idx`` None means the identity: output row ``i`` is probe row
    ``i`` (a probe-order join core), and probe columns are served as they
    are stored, with no gather.  ``build_idx`` may be a pair ``(perm,
    idx)``: a build column is then permuted by ``perm`` at the build
    bucket and gathered by ``idx``, which is ``take(col, perm[idx])``
    without composing the two indices at the output's length.

    Packed columns are stored as narrow codes: the gather moves code-width
    bytes and the decode to logical values runs *after* it, so the expensive
    data movement inside the program happens at packed width and consumers
    of the view still see exact logical values (the decode-at-fetch rule).
    """

    def __init__(self, bcols, pcols, key, build_idx, probe_idx,
                 bdec=None, pdec=None):
        self._bcols = bcols
        self._pcols = pcols
        self._key = key
        self._bidx = (build_idx if isinstance(build_idx, tuple)
                      else (build_idx,))
        self._pidx = probe_idx
        self._bdec = bdec or {}
        self._pdec = pdec or {}
        self._cache: Dict[str, jnp.ndarray] = {}

    def names(self):
        out = list(self._pcols)
        out += [f"b_{n}" for n in self._bcols
                if n != self._key and f"b_{n}" not in out]
        return out

    def __getitem__(self, name: str) -> jnp.ndarray:
        if name not in self._cache:
            # build side resolves first: when a probe column is literally
            # named b_<x> and the build side has x, the engine's join
            # (a dict merge that assigns build columns last) serves the
            # BUILD column under that name — the view must agree
            with jax.named_scope("gather"):
                if (name.startswith("b_") and name[2:] in self._bcols
                        and name[2:] != self._key):
                    col = self._bcols[name[2:]]
                    for idx in self._bidx:
                        col = jnp.take(col, idx)
                    dec = self._bdec.get(name[2:])
                elif name in self._pcols:
                    col = self._pcols[name]
                    if self._pidx is not None:
                        col = jnp.take(col, self._pidx)
                    dec = self._pdec.get(name)
                else:
                    raise KeyError(name)
            with jax.named_scope("decode"):
                self._cache[name] = col if dec is None else dec(col)
        return self._cache[name]


# ---------------------------------------------------------------------------
# Program construction + shape-bucketed compile cache
# ---------------------------------------------------------------------------

class _PipelineCache:
    """Explicit compile cache keyed on the bucketed shape signature.

    jit would deduplicate compilations on its own, but an explicit cache (a)
    avoids re-tracing the program closure per query and (b) exposes hit/miss
    counters that tests use to prove shape bucketing prevents recompile
    churn.

    Thread-safe: concurrent serving sessions share this cache, so lookups,
    counter updates and inserts happen under one lock.  ``builder()`` runs
    inside the lock — it only constructs the jit *wrapper* (cheap; the
    actual XLA compilation happens lazily at first call, which JAX already
    serializes internally), and holding the lock guarantees two racing
    queries of the same shape get the SAME program object, so cache-miss
    accounting stays exact (the warm/cold feedback gate keys off it)."""

    def __init__(self):
        # key -> [program, ready]; ready flips once a call has completed,
        # i.e. XLA compilation is definitely done
        self._programs: Dict[Tuple, list] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key: Tuple, builder: Callable[[], Callable]
            ) -> Tuple[Callable, bool]:
        """Returns ``(program, fresh)``.  ``fresh`` means the next call may
        pay XLA compilation — either this is the first request for the
        shape, or another thread inserted the wrapper and is still inside
        its compiling first call.  Fresh runs execute OUTSIDE the device
        dispatch queue (a racer blocking on JAX's internal compile lock
        while holding the FIFO would stall the whole fleet) and count as
        cache misses, so the executor's warm-feedback gate keeps their
        compile-inclusive walls out of the runtime profile."""
        with self._lock:
            entry = self._programs.get(key)
            if entry is None:
                self.misses += 1
                entry = self._programs[key] = [builder(), False]
                return entry[0], True
            if not entry[1]:
                self.misses += 1  # still compiling somewhere: cold
                return entry[0], True
            self.hits += 1
            return entry[0], False

    def mark_ready(self, key: Tuple) -> None:
        """A call of this program completed: compilation is over."""
        with self._lock:
            entry = self._programs.get(key)
            if entry is not None:
                entry[1] = True

    def info(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "programs": len(self._programs)}

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()
            self.hits = 0
            self.misses = 0


_CACHE = _PipelineCache()


def pipeline_cache_info() -> Dict[str, int]:
    return _CACHE.info()


def pipeline_cache_clear() -> None:
    _CACHE.clear()


def _prefix(x, reducer):
    """Inclusive prefix sum (``reducer`` ``lax.add``) or running max
    (``lax.max``) of a 1-D integer array: the reduce-window that
    ``jnp.cumsum`` and ``lax.cummax`` lower to, written out.  Those
    primitives lower through a separate function, and its inlining leaves
    their ops without the caller's ``jax.named_scope``; written here, the
    same instructions keep the scope."""
    n = x.shape[0]
    init = 0 if reducer is jax.lax.add else jnp.iinfo(x.dtype).min
    return jax.lax.reduce_window(x, np.array(init, x.dtype), reducer, (n,),
                                 (1,), [(n - 1, 0)])


# Merged-sort work per binary-search step at which the two formulations of
# :func:`_align_sorted` cost the same, fitted on a TPU v5e at a 2^20-row
# build: the pair of searches, ~35 ns a probe and step there, against the
# merged sort, 7.2 ms over 2^20 + 2^12..2^17 rows.  The searches then win
# below about 10,000 probes.
_SORT_WORK_PER_SEARCH_STEP = 2000


def _merge_beats_search(B: int, P: int) -> bool:
    """Static choice of :func:`_align_sorted`'s formulation from the bucket
    sizes: ``P·⌈log2(B+1)⌉`` search steps against ``(B+P)·log2(B+P)²`` of
    merged sort work.  Few probes against a large build keep the search."""
    if B == 0 or P == 0:
        return False
    n = B + P
    search = P * B.bit_length()  # B.bit_length() == ⌈log2(B+1)⌉
    return n * math.log2(n) ** 2 < _SORT_WORK_PER_SEARCH_STEP * search


def _align_sorted(sk, pk):
    """``searchsorted(sk, pk, "left")`` and ``searchsorted(sk, pk,
    "right")`` over the ascending build keys ``sk``, equal in value and
    dtype, from one merged sort when the shapes make that cheaper.

    The binary search is a ``while`` loop of random gathers, which the TPU
    does slowly; it sorts quickly.  The merged form sorts the build and
    probe keys together, build rows first among equal keys, so a probe's
    ``right`` is the running count of build rows before it and its
    ``left`` that count at the start of its run of equal keys, forward
    filled by a running max (the count never decreases).  A second sort,
    by probe row, puts both back in probe order.  No gather, no scatter.
    """
    B, P = sk.shape[0], pk.shape[0]
    if not _merge_beats_search(B, P):
        return (jnp.searchsorted(sk, pk, side="left"),
                jnp.searchsorted(sk, pk, side="right"))
    i32 = np.iinfo(np.int32).max
    out = np.int32 if B <= i32 else np.int64  # searchsorted's dtype
    row_t = np.int32 if B + P <= i32 else np.int64
    keys = jnp.concatenate([sk, pk])
    # build rows carry row -1, so each sorts before the probes of its key
    row = jnp.concatenate([jnp.full((B,), -1, row_t),
                           jnp.arange(P, dtype=row_t)])
    keys, row = jax.lax.sort((keys, row), num_keys=2)
    is_build = (row < 0).astype(out)
    right = _prefix(is_build, jax.lax.add)
    run_start = jnp.concatenate([jnp.ones((1,), bool), keys[1:] != keys[:-1]])
    left = _prefix(jnp.where(run_start, right - is_build, 0), jax.lax.max)
    # the B build rows (row -1) sort first; the probes follow in row order
    _, left, right = jax.lax.sort((row, left, right), num_keys=1)
    return left[B:], right[B:]


def _join_sorted(bk, pk, n_build, n_probe, capacity,
                 probe_order: bool = False):
    """General join core: sorted coordinate alignment (a device sort of
    the build side, then :func:`_align_sorted`).

    ``probe_order`` (static) is the form for build keys believed unique:
    each probe row matches at most one build row, so output row ``i`` is
    probe row ``i`` (``probe_idx`` None) at the probe bucket, with no
    expansion.  Its ``build_idx`` is the pair ``(order, left)``, so build
    columns are sorted at the build bucket and gathered by ``left``, and
    the int64 ``order`` is never gathered at the probe bucket.
    ``has_dup`` checks the belief and ``total`` is the join's true size,
    so :func:`run_fused`'s retry on the expanding form takes the right
    capacity at once."""
    B = bk.shape[0]
    P = pk.shape[0]
    iota_b = jnp.arange(B)
    iota_p = jnp.arange(P)
    with jax.named_scope("join.sorted.sort"):
        # bucket padding rows sort to the tail and can never match
        bk_m = jnp.where(iota_b < n_build, bk, _I64_MAX)
        order = jnp.argsort(bk_m, stable=True)
        sk = jnp.take(bk_m, order)
    with jax.named_scope("join.sorted.search"):
        left, right = _align_sorted(sk, pk)
        counts = right - left
        # padded probe rows contribute nothing; a real probe key equal to
        # the int64 sentinel would false-match padded build rows, so it is
        # excluded (documented key-domain contract)
        counts = jnp.where((iota_p < n_probe) & (pk != _I64_MAX), counts, 0)
    if probe_order:
        with jax.named_scope("join.expand"):
            # a probe's one match is the sorted build row at its left bound
            build_idx = (order, jnp.clip(left, 0, B - 1))
            return (build_idx, None, counts > 0, counts.sum(),
                    counts.max() > 1)
    with jax.named_scope("join.prefix_sum"):
        ends = _prefix(counts, jax.lax.add)
        starts = ends - counts
        total = ends[-1]
    with jax.named_scope("join.expand"):
        slot = jnp.arange(capacity, dtype=ends.dtype)
        # expansion by scan, not binary search: scatter each matched probe
        # row's index at its start slot, then forward-fill with a running
        # max
        seed_slots = jnp.full((capacity + 1,), -1, jnp.int64)
        tgt = jnp.where(counts > 0, jnp.minimum(starts, capacity), capacity)
        seeded = seed_slots.at[tgt].max(iota_p)[:capacity]
        probe_idx = jnp.maximum(_prefix(seeded, jax.lax.max), 0)
        build_pos = left[probe_idx] + (slot - starts[probe_idx])
        build_idx = jnp.take(order, jnp.clip(build_pos, 0, B - 1))
        valid = slot < total
    has_dup = jnp.asarray(False)
    return build_idx, probe_idx, valid, total, has_dup


def _join_sorted_run(sk, pk, n_probe, capacity):
    """Join core over a PRE-SORTED build run (the sharded path).

    The partitioned layout (:mod:`repro.core.partition`) stores each build
    partition key-sorted with sentinel padding at the tail, so alignment
    (:func:`_align_sorted`) runs over an already-ordered, cache-resident
    run — **no per-query build sort**.  ``build_idx`` therefore indexes
    the stored run directly (the single-device core needs an ``order``
    indirection because it sorts inside the program).  Expansion is the
    same scatter + running-max forward fill as :func:`_join_sorted`.
    """
    B = sk.shape[0]
    P = pk.shape[0]
    iota_p = jnp.arange(P)
    with jax.named_scope("join.sorted.search"):
        left, right = _align_sorted(sk, pk)
        # sentinel-padded probe rows contribute nothing (same key-domain
        # contract as the single-device core)
        counts = jnp.where((iota_p < n_probe) & (pk != _I64_MAX),
                           right - left, 0)
    with jax.named_scope("join.prefix_sum"):
        ends = _prefix(counts, jax.lax.add)
        starts = ends - counts
        total = ends[-1]
    with jax.named_scope("join.expand"):
        slot = jnp.arange(capacity, dtype=ends.dtype)
        seed_slots = jnp.full((capacity + 1,), -1, jnp.int64)
        tgt = jnp.where(counts > 0, jnp.minimum(starts, capacity), capacity)
        seeded = seed_slots.at[tgt].max(iota_p)[:capacity]
        probe_idx = jnp.maximum(_prefix(seeded, jax.lax.max), 0)
        build_pos = left[probe_idx] + (slot - starts[probe_idx])
        build_idx = jnp.clip(build_pos, 0, B - 1)
        valid = slot < total
    return build_idx, probe_idx, valid, total


def _join_dense(bk, pk, n_build, n_probe, capacity, domain: int, kmin,
                use_kernel: bool = False, probe_order: bool = False):
    """Dense-domain join core: the key IS a coordinate axis.

    When the build key domain is dense enough to materialize as an axis of
    length ``domain`` (a static power-of-two bucket; ``kmin`` is a traced
    offset) and build keys are unique (PK-FK joins), alignment is direct
    scatter/gather addressing — NO device sort at all.  Uniqueness is
    *verified on device* and the flag rides back with the result fetch; the
    driver re-runs on the sorted core if the optimistic choice was wrong.
    Slot ``domain`` of every scatter target is the spill-over slot for rows
    that must not write (bucket padding / out-of-domain keys).

    ``use_kernel`` (static) routes the table build + probe through the
    Pallas radix-join kernels (:mod:`repro.kernels.segment_join`) via
    :func:`~repro.core.tensor_engine.radix_hash_probe_dispatch` — the
    in-domain codes ``bk0c``/``pk0c`` are exactly the int32 code-domain
    contract those kernels tile over, and the dead slot ``domain`` is
    their padding slot.  Results are bit-for-bit the jnp scatter path's
    (kernel parity is regression-tested in tests/test_kernels.py).

    ``probe_order`` (static) returns the rows in probe order at the probe
    bucket (``probe_idx`` None), as :func:`_join_sorted` does; the
    expanding form compacts the matched probe rows to the front.
    """
    B = bk.shape[0]
    P = pk.shape[0]
    iota_b = jnp.arange(B)
    iota_p = jnp.arange(P)
    with jax.named_scope("join.dense.build"):
        bk0 = bk - kmin
        b_live = iota_b < n_build
        bk0c = jnp.where(b_live & (bk0 >= 0) & (bk0 < domain), bk0, domain)
    with jax.named_scope("join.dense.probe"):
        pk0 = pk - kmin
        p_live = (iota_p < n_probe) & (pk0 >= 0) & (pk0 < domain)
        pk0c = jnp.where(p_live, pk0, domain)
    if use_kernel:
        with jax.named_scope("join.dense.pallas"):
            cnt_p, brow, has_dup = radix_hash_probe_dispatch(
                bk0c.astype(jnp.int32), pk0c.astype(jnp.int32), domain,
                True)
            matched = p_live & (cnt_p > 0)
    else:
        with jax.named_scope("join.dense.build"):
            cnt = jnp.zeros((domain + 1,), jnp.int32).at[bk0c].add(1)
            has_dup = cnt[:domain].max() > 1
            inv = jnp.zeros((domain + 1,), jnp.int64).at[bk0c].set(iota_b)
        with jax.named_scope("join.dense.probe"):
            matched = p_live & (cnt[pk0c] > 0)
    with jax.named_scope("join.expand"):
        # each probe row's build row (read only where it matched)
        hit = (jnp.maximum(brow, 0) if use_kernel
               else jnp.take(inv, pk0c))
        if probe_order:
            return hit, None, matched, matched.sum(), has_dup
    with jax.named_scope("join.prefix_sum"):
        ends = _prefix(matched.astype(jnp.int64), jax.lax.add)
        total = ends[-1]
    with jax.named_scope("join.expand"):
        slot = jnp.arange(capacity, dtype=jnp.int64)
        pos = jnp.where(matched, jnp.minimum(ends - 1, capacity - 1),
                        capacity)
        probe_idx = jnp.zeros((capacity + 1,),
                              jnp.int64).at[pos].max(iota_p)[:capacity]
        build_idx = jnp.take(hit, probe_idx)
        valid = slot < total
    return build_idx, probe_idx, valid, total, has_dup


def _build_program(spec: FusedSpec, key: str, capacity: int,
                   dense_domain: Optional[int] = None,
                   key_mode: str = "value", use_kernel: bool = False,
                   bsig: Tuple = (), psig: Tuple = (),
                   probe_order: bool = False):
    """Trace-time closure for one (fragment, capacity, bucket) cache entry.

    ``dense_domain`` (a static power-of-two bucket) selects the sort-free
    coordinate join core; the domain offset ``kmin`` stays a traced scalar so
    drifting key ranges reuse the compiled program.  ``probe_order`` selects
    the join cores' form for unique build keys: rows stay in probe order
    at the probe bucket (``capacity`` must be it) and nothing expands.

    ``bsig``/``psig`` are the static per-column layout signatures
    (:meth:`~repro.core.codec_device.DeviceColumnLayout.signature`) of the
    packed inputs — the program closes over the codec *shape*; dictionaries
    and reference points stay runtime inputs so data refreshes never
    recompile.  ``key_mode`` selects the join coordinate domain:

      * ``"value"`` — the key decodes to int64 values in-program (an
        elementwise op; the H2D transfer already happened at packed width)
        and the join cores run exactly as before;
      * ``"dict"``  — the build key is dictionary-encoded and the join runs
        *directly in the code domain*: build codes are the coordinates,
        probe values remap into the build dictionary with one device
        ``searchsorted`` (misses land on the dead slot), and the dense core
        operates over ``dense_domain ==`` the padded dictionary bucket.
        The key axis never widens to int64 coordinates at all.
    """

    def program(bcols: Dict[str, jnp.ndarray], pcols: Dict[str, jnp.ndarray],
                bdicts, pdicts, brefs, prefs, n_build, n_probe, kmin):
        bdec = _decoders(bsig, bdicts, brefs)
        pdec = _decoders(psig, pdicts, prefs)
        if key_mode == "dict":
            # code-domain join: build codes ARE the coordinates; the probe
            # side remaps its logical key values into the build dictionary
            # (padded with repeats of the last value — searchsorted-left
            # still returns the true first occurrence; see pad_dictionary)
            with jax.named_scope("decode"):
                bk = bcols[key].astype(jnp.int64)
                pk_raw = pcols[key]
                pk_vals = (pk_raw if pdec.get(key) is None
                           else pdec[key](pk_raw)).astype(jnp.int64)
                bdict = bdicts[key].astype(jnp.int64)
            dbkt = bdict.shape[0]
            with jax.named_scope("join.dict.remap"):
                pos = jnp.searchsorted(bdict, pk_vals, side="left")
                posc = jnp.clip(pos, 0, dbkt - 1)
                hit = jnp.take(bdict, posc) == pk_vals
                pk = jnp.where(hit, posc, dense_domain).astype(jnp.int64)
        else:
            # join coordinates are int64 (same coercion as tensor_join); the
            # view/output below serves the ORIGINAL key column — dtype and
            # values of result columns never depend on fusion
            with jax.named_scope("decode"):
                bk_raw, pk_raw = bcols[key], pcols[key]
                bk = (bk_raw if bdec.get(key) is None
                      else bdec[key](bk_raw)).astype(jnp.int64)
                pk = (pk_raw if pdec.get(key) is None
                      else pdec[key](pk_raw)).astype(jnp.int64)
        if dense_domain is not None:
            build_idx, probe_idx, valid, total, has_dup = _join_dense(
                bk, pk, n_build, n_probe, capacity, dense_domain, kmin,
                use_kernel=use_kernel, probe_order=probe_order)
        else:
            build_idx, probe_idx, valid, total, has_dup = _join_sorted(
                bk, pk, n_build, n_probe, capacity, probe_order)

        view = _JoinView(bcols, pcols, key, build_idx, probe_idx, bdec, pdec)
        if spec.filter_fn is not None:
            with jax.named_scope("filter"):
                mask = jnp.asarray(spec.filter_fn(view), bool)
                valid = valid & mask

        perm = None
        if spec.sort_keys:
            # ONE multi-operand lexicographic device sort: key axes stay
            # separate operands (no linearization into a composite scalar)
            # and the permutation rides as the trailing payload.  Invalid
            # rows sink by pinning their most-significant key to the dtype
            # maximum — their relative position among real max-key rows is
            # irrelevant because only valid rows survive materialization.
            with jax.named_scope("sort"):
                keys0 = [view[k] for k in spec.sort_keys]
                msk = keys0[0]
                if jnp.issubdtype(msk.dtype, jnp.integer):
                    fill = jnp.iinfo(msk.dtype).max
                else:
                    fill = jnp.inf
                operands = [jnp.where(valid, msk, fill)] + keys0[1:]
                operands.append(jnp.arange(capacity, dtype=jnp.int32))
                sorted_ops = jax.lax.sort(tuple(operands), dimension=0,
                                          is_stable=True,
                                          num_keys=len(operands) - 1)
                perm = sorted_ops[-1]

        if spec.agg is not None:
            fn = spec.agg[1]
            with jax.named_scope("aggregate"):
                col = spec.agg_column(view)
                col = jnp.broadcast_to(col, valid.shape)
                v = valid if perm is None else jnp.take(valid, perm)
                c = col if perm is None else jnp.take(col, perm)
                # integer columns reduce in int64 (exact, matches the host
                # path bit-for-bit — f64 would lose integer sums past 2^53)
                is_int = jnp.issubdtype(c.dtype, jnp.integer)
                if fn == "sum":
                    zero = jnp.asarray(0, c.dtype)
                    scalar = jnp.where(v, c, zero).sum()
                elif fn == "count":
                    scalar = v.sum().astype(jnp.int64)
                elif fn == "min":
                    fill = jnp.iinfo(c.dtype).max if is_int else jnp.inf
                    scalar = jnp.where(v, c, fill).min()
                elif fn == "max":
                    fill = jnp.iinfo(c.dtype).min if is_int else -jnp.inf
                    scalar = jnp.where(v, c, fill).max()
                else:
                    raise ValueError(fn)
                agg_n = v.sum()
            # agg_n rides the fetch so the driver can reject min/max over an
            # empty result (the fill value is not a legitimate answer) the
            # way the host path's numpy reduction does
            return {"total": total, "has_dup": has_dup, "scalar": scalar,
                    "agg_n": agg_n}

        # relation root (sort is the last stage): gather the output schema
        # through the sorted indices — the only payload gathers in the
        # whole pipeline, and they happen once, on device.  A projected
        # root gathers (and later fetches) only its declared subset.
        out_names = view.names() if spec.project is None else spec.project
        with jax.named_scope("gather"):
            out_cols = {name: (view[name] if perm is None
                               else jnp.take(view[name], perm))
                        for name in out_names}
            out_valid = valid if perm is None else jnp.take(valid, perm)
        return {"total": total, "has_dup": has_dup, "cols": out_cols,
                "valid": out_valid}

    return jax.jit(program)


# ---------------------------------------------------------------------------
# Sharded program: partition-parallel fragment over a device mesh
# ---------------------------------------------------------------------------

def sharded_supported(spec: FusedSpec, build: Relation,
                      probe: Relation) -> bool:
    """Host-side eligibility of a fragment for partition-parallel execution.

    The sharded path merges per-partition results with device-side
    combines (psum/pmin/pmax over the mesh axis), so only scalar
    AGGREGATE roots qualify — a relation root would need a global merge
    that re-serializes the partitions.  Bit-for-bit parity with the
    single-device program is part of the contract, which admits exactly
    the order-independent reductions: ``count`` always; ``min``/``max``
    always (exact for floats too); ``sum`` only over integer columns —
    integer addition is associative even under wraparound, while a float
    psum of per-partition partials reassociates the single program's
    reduction order.  Join keys must be integers (the partition hash and
    the sentinel padding contract are int64).  A fragment's sort stage is
    irrelevant under these aggregates and is skipped per shard.
    """
    if spec.agg is None:
        return False
    key = spec.join_key
    for rel in (build, probe):
        if not isinstance(rel, Relation) or key not in rel.names:
            return False
        if not np.issubdtype(rel[key].dtype, np.integer):
            return False
    col, fn = spec.agg
    if fn == "count":
        return True

    def dtype_of(name):
        # the _JoinView naming contract: build wins b_<x> collisions
        if (name.startswith("b_") and name[2:] in build.names
                and name[2:] != key):
            return build[name[2:]].dtype
        return probe[name].dtype if name in probe.names else None

    if spec.measure is None:
        dtype = dtype_of(col)
    else:
        # the measure's dtype, from one row of ones of each column it reads
        dts = {c: dtype_of(c) for c in spec.measure.columns()}
        if any(dt is None for dt in dts.values()):
            return False
        dtype = np.asarray(spec.measure(
            {c: np.ones(1, dt) for c, dt in dts.items()})).dtype
    if dtype is None:
        return False
    if fn in ("min", "max"):
        return True
    return fn == "sum" and bool(np.issubdtype(dtype, np.integer))


def _build_sharded_program(spec: FusedSpec, key: str, num_parts: int,
                           capacity: int, bsig: Tuple = (),
                           psig: Tuple = ()):
    """Trace-time closure for one sharded (fragment, partitions, capacity)
    cache entry: the per-shard fragment body under ``shard_map`` over the
    relational mesh, with device-side combines so the host still fetches
    ONE replicated result dict per query.

    ``max_part_total`` (the largest single partition's match count) rides
    the fetch next to the psum'd total so the driver can verify its
    optimistic per-partition capacity without a second sync.

    Payload columns arrive as packed codes (``bsig``/``psig`` carry the
    static layout signatures); dictionaries and reference points are
    REPLICATED runtime inputs — every shard decodes at gather against the
    full dictionary, and a data refresh never recompiles.  The join key
    stays logical int64 (the sentinel-padding contract).
    """
    from jax.sharding import PartitionSpec as PSpec

    from ..distributed.sharding import PART_AXIS, relational_mesh

    mesh = relational_mesh(num_parts)
    fn = spec.agg[1]

    def shard_body(bcols, pcols, bdicts, pdicts, brefs, prefs,
                   n_build, n_probe):
        # each shard sees a (1, bucket) block of its partition: squeeze
        bcols = {k: v[0] for k, v in bcols.items()}
        pcols = {k: v[0] for k, v in pcols.items()}
        bdec = _decoders(bsig, bdicts, brefs)
        pdec = _decoders(psig, pdicts, prefs)
        del n_build  # build padding is sentinel-keyed; no live-row mask
        npr = n_probe[0]
        sk = bcols[key].astype(jnp.int64)
        pk = pcols[key].astype(jnp.int64)
        build_idx, probe_idx, valid, total = _join_sorted_run(
            sk, pk, npr, capacity)
        view = _JoinView(bcols, pcols, key, build_idx, probe_idx,
                         bdec, pdec)
        if spec.filter_fn is not None:
            mask = jnp.asarray(spec.filter_fn(view), bool)
            valid = valid & mask
        # sort stage intentionally skipped: the supported aggregates are
        # order-independent (see sharded_supported)
        if fn == "count":
            part = valid.sum().astype(jnp.int64)
            scalar = jax.lax.psum(part, PART_AXIS)
        else:
            c = jnp.broadcast_to(spec.agg_column(view), valid.shape)
            is_int = jnp.issubdtype(c.dtype, jnp.integer)
            if fn == "sum":
                zero = jnp.asarray(0, c.dtype)
                part = jnp.where(valid, c, zero).sum()
                scalar = jax.lax.psum(part, PART_AXIS)
            elif fn == "min":
                fill = jnp.iinfo(c.dtype).max if is_int else jnp.inf
                part = jnp.where(valid, c, fill).min()
                scalar = jax.lax.pmin(part, PART_AXIS)
            elif fn == "max":
                fill = jnp.iinfo(c.dtype).min if is_int else -jnp.inf
                part = jnp.where(valid, c, fill).max()
                scalar = jax.lax.pmax(part, PART_AXIS)
            else:
                raise ValueError(fn)
        return {"total": jax.lax.psum(total, PART_AXIS),
                "max_part_total": jax.lax.pmax(total, PART_AXIS),
                "scalar": scalar,
                "agg_n": jax.lax.psum(valid.sum(), PART_AXIS)}

    mapped = jax.shard_map(shard_body, mesh=mesh,
                           in_specs=(PSpec(PART_AXIS), PSpec(PART_AXIS),
                                     PSpec(), PSpec(), PSpec(), PSpec(),
                                     PSpec(PART_AXIS), PSpec(PART_AXIS)),
                           out_specs=PSpec())
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

# The device is a serially-shared resource: concurrent serving sessions
# funnel fused-program launches through the broker's DeviceQueue (a typed
# DeviceLease per dispatch), so a query's device phase runs at full speed
# instead of time-slicing against seven neighbors (the scheduler roulette
# that turns a homogeneous workload into a 3x p99/p50 spread).  Latency
# becomes queue wait + execution — the wait is accounted in
# OpMetrics.queue_wait_s and excluded from the runtime profile's
# execution-cost observations.  Queued dispatches of the SAME compiled
# shape (lease batch_key = the pipeline cache key) coalesce into one
# micro-batched admission group instead of running strictly one-at-a-time;
# the programs are identical compiled artifacts over independent inputs, so
# coalescing changes scheduling only, never results.
# ``REPRO_DEVICE_SERIALIZE=0`` makes the broker grant device leases without
# serializing (e.g. multi-device hosts where XLA can genuinely overlap
# programs).


def _host_plan(build: Relation, probe: Relation, key: str):
    """Host-side planning from the numpy inputs — free of device traffic.

    Returns ``(capacity, dense_domain, kmin, probe_order)``: an optimistic
    capacity bucket from the cached key-cardinality sketch
    (:func:`repro.core.table_cache.key_stats` — repeated queries do not
    re-sample); when the build key domain is dense enough to materialize as
    a coordinate axis and the sample predicts unique keys, the power-of-two
    domain bucket for the sort-free dense join core; and whether the sample
    predicts unique keys, so the join core can keep its rows in probe order
    at the probe bucket.  The predictions are *verified on device*
    (overflow / has_dup piggyback on the result fetch), so a wrong guess
    costs one retry, never a wrong answer.
    """
    stats = key_stats(build, key)
    capacity = capacity_bucket(int(len(probe) * stats.dup))
    # the probe-order cores' output is the probe bucket itself
    probe_order = (stats.dup == 1.0
                   and capacity == capacity_bucket(len(probe)))
    dense_domain = None
    kmin = 0
    if stats.dup == 1.0 and stats.n:
        kmin = int(stats.kmin)
        width = int(stats.kmax) - kmin + 1
        if width <= 4 * capacity_bucket(stats.n):
            dense_domain = capacity_bucket(width)
    return capacity, dense_domain, kmin, probe_order


def run_fused(spec: FusedSpec, build: Relation, probe: Relation,
              decision_reason: str = "", broker=None,
              shards: Optional[int] = None,
              guard=None) -> Tuple[object, OpMetrics]:
    """Execute a fused fragment; returns (Relation | float, OpMetrics).

    Happy path: one compiled program launch + one batched device→host fetch.
    Capacity overflow (optimistic bucket too small) re-runs once at the exact
    bucket; both programs stay cached for subsequent queries.  A build key
    the sample finds unique runs the probe-order join cores; a repeat the
    sample missed is caught on device (``has_dup``) and re-runs once on the
    expanding core, at the join's true size.

    Device dispatch acquires a :class:`~repro.core.resource_broker.
    DeviceLease` from ``broker`` (the process-wide default broker when none
    is passed — one shared queue per physical device); queued dispatches of
    the same compiled shape coalesce into one micro-batched admission group.

    ``shards=N`` (N >= 2) requests partition-parallel execution over the
    first N mesh devices: hash/radix co-partition both sides by the join
    key, run the fragment per partition under ``shard_map``, and combine
    per-partition aggregates on device — still ≤ 1 device→host sync.  The
    request silently degrades to the single-device path when the fragment
    is not :func:`sharded_supported` or fewer devices exist (metrics then
    report ``devices=1``); dispatch holds a gang lease over one broker
    lane per device.

    ``guard`` is an optional :class:`~repro.core.guards.ExecutionGuard`:
    a capacity overflow — the device reporting the ACTUAL join fan-out —
    is fed to ``guard.observe_fragment`` before the retry, which may raise
    :class:`~repro.core.guards.SwitchPoint` to abandon the retry loop when
    the re-priced linear fragment beats a second dispatch at the exact
    bucket (the executor's generic walk then re-plans with ground truth).
    """
    if broker is None:
        from .resource_broker import default_broker
        broker = default_broker()
    if shards is not None and int(shards) > 1:
        from ..distributed.sharding import available_partitions

        num_parts = min(int(shards), available_partitions())
        if num_parts > 1 and sharded_supported(spec, build, probe):
            return _run_fused_sharded(spec, build, probe, num_parts,
                                      decision_reason, broker)
    n_build, n_probe = len(build), len(probe)
    b_bucket = capacity_bucket(n_build)
    p_bucket = capacity_bucket(n_probe)
    syncs = 0
    queue_wait = 0.0
    any_fresh = False
    batched = False
    with Timer() as t:
        # host planning is part of the query's wall time (the per-op
        # baseline pays for its planning inside its timers too)
        with tracing.span("rel.host_prep"):
            capacity, dense_domain, kmin, probe_order = _host_plan(
                build, probe, spec.join_key)
            layouts_b, up_b, log_b = get_device_layouts(build, b_bucket)
            layouts_p, up_p, log_p = get_device_layouts(probe, p_bucket)
            bcols = {k: dc.codes for k, dc in layouts_b.items()}
            pcols = {k: dc.codes for k, dc in layouts_p.items()}
            bdicts = {k: dc.dict_values for k, dc in layouts_b.items()
                      if dc.dict_values is not None}
            pdicts = {k: dc.dict_values for k, dc in layouts_p.items()
                      if dc.dict_values is not None}
            brefs = {k: dc.layout.ref for k, dc in layouts_b.items()
                     if dc.encoding == "for"}
            prefs = {k: dc.layout.ref for k, dc in layouts_p.items()
                     if dc.encoding == "for"}
            bsig = tuple(sorted((k, dc.layout.signature())
                                for k, dc in layouts_b.items()))
            psig = tuple(sorted((k, dc.layout.signature())
                                for k, dc in layouts_p.items()))
            # Dictionary-encoded build key + sampled-unique keys: join in
            # the code domain — the dense core over the padded dictionary
            # bucket, even when the VALUE domain is far too wide/sparse for
            # it.  A wrong uniqueness guess is caught on device (has_dup)
            # and retried on the sorted value core, same as the value-dense
            # path.
            key_mode = "value"
            bkey = layouts_b[spec.join_key]
            if bkey.encoding == "dict":
                stats = key_stats(build, spec.join_key)
                if stats.dup == 1.0 and stats.n:
                    key_mode = "dict"
                    dense_domain = dict_bucket(bkey.layout.card)
                    kmin = 0
        while True:
            with tracing.span("rel.host_prep"):
                use_kernel = (use_pallas(dense_domain)
                              if dense_domain is not None else False)
                cache_key = (spec.cache_signature(), capacity, b_bucket,
                             p_bucket, dense_domain, key_mode, use_kernel,
                             bsig, psig, probe_order)
                prog, fresh = _CACHE.get(
                    cache_key,
                    lambda: _build_program(spec, spec.join_key, capacity,
                                           dense_domain, key_mode,
                                           use_kernel, bsig, psig,
                                           probe_order))
            # a FRESH program's first call pays multi-second XLA
            # compilation; running it outside the queue keeps one novel
            # shape from stalling every other query's device phase (its
            # own unserialized execution is a one-off, and compiling runs
            # never feed the runtime profile anyway)
            any_fresh = any_fresh or fresh
            tracing.count(fresh_programs=int(fresh), dispatches=1,
                          probe_order_joins=int(probe_order))
            lease = None
            if not fresh:
                lease = broker.device_lease(batch_key=("fused", cache_key))
                queue_wait += lease.wait_s
            try:
                with tracing.span("rel.compile" if fresh
                                  else "rel.dispatch"):
                    out = prog(bcols, pcols, bdicts, pdicts, brefs, prefs,
                               n_build, n_probe, kmin)
                with tracing.span("rel.fetch"):
                    # THE host sync of the query
                    fetched = jax.device_get(out)
            finally:
                if lease is not None:
                    lease.release()
                    # read AFTER the run: `batched` is live — a solo lease
                    # becomes batched when a same-shape arrival joins its
                    # in-flight round
                    batched = batched or lease.batched
            if fresh:
                _CACHE.mark_ready(cache_key)
            syncs += 1
            total = int(fetched["total"])
            retry = False
            if ((dense_domain is not None or probe_order)
                    and bool(fetched["has_dup"])):
                # optimistic unique-key guess was wrong: fall back to the
                # expanding sorted core over decoded int64 values (code-
                # domain joins included — the sorted core's sentinel
                # contract is int64)
                dense_domain = None
                key_mode = "value"
                kmin = 0
                probe_order = False
                retry = True
            if total > capacity:
                if guard is not None:
                    # the overflow IS the observed fan-out: let the
                    # execution-time guard re-check the fragment decision
                    # before paying the retry dispatch (raises SwitchPoint
                    # to abandon)
                    guard.observe_fragment(total, capacity)
                capacity = capacity_bucket(total)  # rare: bucket overflowed
                retry = True
            if not retry:
                break
            tracing.count(retries=1)
        with tracing.span("rel.assemble"):
            if spec.agg is not None:
                if (spec.agg[1] in ("min", "max")
                        and int(fetched["agg_n"]) == 0):
                    raise ValueError(f"{spec.agg[1]} over an empty result "
                                     f"has no identity")
                result = float(fetched["scalar"])
                rows_out = 1
            else:
                keep = np.nonzero(np.asarray(fetched["valid"]))[0]
                result = Relation({k: np.asarray(v)[keep]
                                   for k, v in fetched["cols"].items()})
                rows_out = len(result)
    metrics = OpMetrics(
        op="fused_pipeline",
        path="tensor",
        rows_in=n_build + n_probe,
        rows_out=rows_out,
        wall_s=t.elapsed,
        spill=SpillAccount(),
        peak_working_set_bytes=(b_bucket + p_bucket) * 8 * 3
        + capacity * 8 * (3 + len(spec.sort_keys)),
        decision_reason=decision_reason,
        host_syncs=syncs,
        h2d_bytes=up_b + up_p,
        h2d_bytes_logical=log_b + log_p,
        queue_wait_s=queue_wait,
        compiled=any_fresh,
        batched=batched,
    )
    return result, metrics


# Verified per-partition capacities by (fragment, partitions, key-column
# tokens): content-addressed, so a mutated table simply misses and re-plans.
# Bounded as a backstop; overflow costs at most one extra retry per entry.
_CAP_HINTS: Dict[tuple, int] = {}
_CAP_HINT_LOCK = threading.Lock()
_CAP_HINTS_CAP = 512


def _run_fused_sharded(spec: FusedSpec, build: Relation, probe: Relation,
                       num_parts: int, decision_reason: str,
                       broker) -> Tuple[float, OpMetrics]:
    """Partition-parallel driver: cached partitioned layouts in, ONE gang
    dispatch over ``num_parts`` broker lanes, ONE replicated fetch out.

    The per-partition capacity is optimistic — the critical partition's
    probe fill times the sampled duplication factor, with skew slack — and
    verified on device: ``max_part_total`` rides the single result fetch,
    a wrong guess costs one retry at the exact bucket, never a wrong
    answer (the same discipline as the single-device driver's overflow
    and dense retries).
    """
    from .partition import get_partitioned_columns, partition_bucket
    from .relation import column_token

    n_build, n_probe = len(build), len(probe)
    syncs = 0
    queue_wait = 0.0
    any_fresh = False
    batched = False
    broker.ensure_lanes(num_parts)
    with Timer() as t:
        with tracing.span("rel.host_prep"):
            stats = key_stats(build, spec.join_key)
            (bcols, counts_b_dev, counts_b, bucket_b, up_b, log_b, b_lay,
             bdicts) = get_partitioned_columns(build, spec.join_key,
                                               num_parts, sort_within=True)
            (pcols, counts_p_dev, counts_p, bucket_p, up_p, log_p, p_lay,
             pdicts) = get_partitioned_columns(probe, spec.join_key,
                                               num_parts, sort_within=False)
            brefs = {k: lay.ref for k, lay in b_lay.items()
                     if lay.encoding == "for"}
            prefs = {k: lay.ref for k, lay in p_lay.items()
                     if lay.encoding == "for"}
            bsig = tuple(sorted((k, lay.signature())
                                for k, lay in b_lay.items()))
            psig = tuple(sorted((k, lay.signature())
                                for k, lay in p_lay.items()))
            est_part_out = int(max(1, int(counts_p.max())) * stats.dup)
            capacity = partition_bucket(int(est_part_out * 1.25))
            # A verified-capacity hint from an earlier run of this fragment
            # over the same data: the optimistic estimate is recomputed per
            # call, so without the hint a query whose critical partition
            # overflows it would pay the overflow retry (a second dispatch
            # + fetch) on EVERY warm serving query, not just the first.
            hint_key = (spec.cache_signature(), num_parts,
                        column_token(build[spec.join_key]),
                        column_token(probe[spec.join_key]))
            with _CAP_HINT_LOCK:
                capacity = max(capacity, _CAP_HINTS.get(hint_key, 0))
        while True:
            with tracing.span("rel.host_prep"):
                cache_key = ("sharded", spec.cache_signature(), num_parts,
                             capacity, bucket_b, bucket_p, bsig, psig)
                prog, fresh = _CACHE.get(
                    cache_key,
                    lambda: _build_sharded_program(spec, spec.join_key,
                                                   num_parts, capacity,
                                                   bsig, psig))
            any_fresh = any_fresh or fresh
            tracing.count(fresh_programs=int(fresh), dispatches=1)
            # ALWAYS under the gang lease — including the compile dispatch.
            # A sharded launch runs collectives over every lane's device;
            # any unleased dispatch (the old fresh-path bypass) can overlap
            # another thread's leased launch and deadlock the host-platform
            # collective rendezvous.
            lease = broker.device_lease(lanes=num_parts)
            queue_wait += lease.wait_s
            try:
                with tracing.span("rel.compile" if fresh
                                  else "rel.dispatch"):
                    out = prog(bcols, pcols, bdicts, pdicts, brefs, prefs,
                               counts_b_dev, counts_p_dev)
                with tracing.span("rel.fetch"):
                    # THE host sync of the query
                    fetched = jax.device_get(out)
            finally:
                lease.release()
                batched = batched or lease.batched
            if fresh:
                _CACHE.mark_ready(cache_key)
            syncs += 1
            max_part = int(fetched["max_part_total"])
            if max_part <= capacity:
                # remember the verified minimal bucket (max() keeps it from
                # ever shrinking a future optimistic estimate)
                with _CAP_HINT_LOCK:
                    if len(_CAP_HINTS) >= _CAP_HINTS_CAP:
                        _CAP_HINTS.clear()
                    _CAP_HINTS[hint_key] = max(
                        _CAP_HINTS.get(hint_key, 0),
                        partition_bucket(max_part))
                break
            capacity = partition_bucket(max_part)  # rare: skewed overflow
            tracing.count(retries=1)
        with tracing.span("rel.assemble"):
            if spec.agg[1] in ("min", "max") and int(fetched["agg_n"]) == 0:
                raise ValueError(
                    f"{spec.agg[1]} over an empty result has no identity")
            result = float(fetched["scalar"])
    metrics = OpMetrics(
        op="fused_pipeline",
        path="tensor",
        rows_in=n_build + n_probe,
        rows_out=1,
        wall_s=t.elapsed,
        spill=SpillAccount(),
        peak_working_set_bytes=num_parts * (bucket_b + bucket_p) * 8 * 3
        + num_parts * capacity * 8 * 3,
        decision_reason=decision_reason,
        host_syncs=syncs,
        h2d_bytes=up_b + up_p,
        h2d_bytes_logical=log_b + log_p,
        queue_wait_s=queue_wait,
        compiled=any_fresh,
        batched=batched,
        devices=num_parts,
    )
    return result, metrics
