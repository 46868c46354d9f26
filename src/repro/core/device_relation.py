"""Device-resident columnar relation with late materialization.

The seed engine lowered every intermediate back to a host-numpy
:class:`~repro.core.relation.Relation` between operators — exactly the
"premature materialization" the paper argues against.  A
:class:`DeviceRelation` keeps columns as JAX device arrays across operators
and carries two pieces of deferred state instead of moving payload bytes:

  * a **pending gather index** per column (late materialization): a join or
    sort does not shuffle payload columns, it composes an ``int`` index array;
    the gather runs on device only when a column is actually consumed;
  * a **validity mask** over the (statically shaped) physical rows: joins
    produce ``capacity``-padded index spaces, filters AND their predicate into
    the mask, and no compaction (a dynamic-shape operation jit cannot express)
    ever happens on device.

Host materialization happens exactly once, at the query root, via
:meth:`to_host` — a single batched ``jax.device_get`` for all columns plus the
mask.  Callers that track :class:`~repro.core.metrics.OpMetrics` count that as
one host sync.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .relation import Relation

__all__ = ["DeviceColumn", "DeviceRelation"]


@dataclasses.dataclass(frozen=True)
class DeviceColumn:
    """A device array plus an optional pending gather index and decode hook.

    The logical column is ``decode(base[gather])`` (gather/decode optional),
    but both are deferred until :meth:`force` — composing two takes costs
    one index gather, never a payload gather, and a packed column
    (:mod:`repro.core.codec_device`) stays narrow codes through every lazy
    composition: the decode to logical width runs on device only when a
    consumer actually reads values (the decode-at-fetch rule).
    """

    base: jnp.ndarray
    gather: Optional[jnp.ndarray] = None
    # device-side decode applied after the gather (packed codes → logical
    # values); None for plain columns.  ``out_dtype`` is the decoded dtype.
    decode: Optional[object] = None
    out_dtype: Optional[object] = None

    def force(self) -> jnp.ndarray:
        arr = self.force_codes()
        if self.decode is not None:
            arr = self.decode(arr)
        return arr

    def force_codes(self) -> jnp.ndarray:
        """The physical (still-packed) column — code-domain consumers
        (group-by factorization) skip the decode entirely."""
        if self.gather is None:
            return self.base
        return jnp.take(self.base, self.gather, axis=0)

    def take_lazy(self, idx: jnp.ndarray) -> "DeviceColumn":
        if self.gather is None:
            return DeviceColumn(self.base, idx, self.decode, self.out_dtype)
        return DeviceColumn(self.base, jnp.take(self.gather, idx, axis=0),
                            self.decode, self.out_dtype)

    @property
    def num_rows(self) -> int:
        arr = self.gather if self.gather is not None else self.base
        return int(arr.shape[0])

    @property
    def dtype(self):
        if self.decode is not None and self.out_dtype is not None:
            return jnp.dtype(self.out_dtype)
        return self.base.dtype


class DeviceRelation:
    """Columns on device; physical rows are static, logical rows are masked."""

    def __init__(self, columns: Dict[str, DeviceColumn],
                 valid: Optional[jnp.ndarray] = None):
        if not columns:
            raise ValueError("DeviceRelation needs at least one column")
        lengths = {k: c.num_rows for k, c in columns.items()}
        if len(set(lengths.values())) != 1:
            raise ValueError(f"ragged device columns: {lengths}")
        self.columns = columns
        self.valid = valid  # None = all physical rows are logical rows

    # -- construction ------------------------------------------------------
    @staticmethod
    def from_host(rel: Relation) -> "DeviceRelation":
        return DeviceRelation(
            {k: DeviceColumn(jnp.asarray(v)) for k, v in rel.columns.items()})

    @staticmethod
    def from_arrays(cols: Mapping[str, jnp.ndarray],
                    valid: Optional[jnp.ndarray] = None) -> "DeviceRelation":
        return DeviceRelation({k: DeviceColumn(v) for k, v in cols.items()},
                              valid=valid)

    @staticmethod
    def from_codes(cols: Mapping[str, object]) -> "DeviceRelation":
        """Lift packed device columns (:class:`~repro.core.codec_device.
        DeviceCodes`) into a relation of decode-deferred columns: storage
        stays at code width, the decode hook runs at :meth:`DeviceColumn.
        force` — i.e. only for columns a consumer actually touches."""
        out: Dict[str, DeviceColumn] = {}
        for k, dc in cols.items():
            if dc.encoding == "raw":
                out[k] = DeviceColumn(dc.codes)
            else:
                out[k] = DeviceColumn(dc.codes, decode=dc.decode,
                                      out_dtype=dc.layout.logical_dtype)
        return DeviceRelation(out)

    # -- properties --------------------------------------------------------
    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(self.columns.keys())

    @property
    def num_physical_rows(self) -> int:
        return next(iter(self.columns.values())).num_rows

    def __len__(self) -> int:
        # Upper bound on logical rows without a device sync; exact count
        # requires materializing the mask (the selector only needs scale).
        return self.num_physical_rows

    def row_bytes(self) -> int:
        return int(sum(c.dtype.itemsize for c in self.columns.values()))

    def col(self, name: str) -> jnp.ndarray:
        """The logical column as a device array (runs the pending gather)."""
        return self.columns[name].force()

    def __getitem__(self, name: str) -> jnp.ndarray:
        return self.col(name)

    # -- transforms (all lazy / device-side, never a host sync) ------------
    def take_lazy(self, idx: jnp.ndarray,
                  valid: Optional[jnp.ndarray] = None) -> "DeviceRelation":
        """Row selection by device index array; payload gathers stay pending.

        Columns sharing one physical gather array compose it once.
        """
        composed: Dict[int, jnp.ndarray] = {}
        out: Dict[str, DeviceColumn] = {}
        for k, c in self.columns.items():
            if c.gather is None:
                out[k] = DeviceColumn(c.base, idx, c.decode, c.out_dtype)
                continue
            key = id(c.gather)
            if key not in composed:
                composed[key] = jnp.take(c.gather, idx, axis=0)
            out[k] = DeviceColumn(c.base, composed[key], c.decode,
                                  c.out_dtype)
        new_valid = valid
        if new_valid is None and self.valid is not None:
            new_valid = jnp.take(self.valid, idx, axis=0)
        return DeviceRelation(out, valid=new_valid)

    def with_valid(self, valid: jnp.ndarray) -> "DeviceRelation":
        return DeviceRelation(dict(self.columns), valid=valid)

    def mask_and(self, mask: jnp.ndarray) -> "DeviceRelation":
        valid = mask if self.valid is None else (self.valid & mask)
        return DeviceRelation(dict(self.columns), valid=valid)

    def select(self, names: Iterable[str]) -> "DeviceRelation":
        return DeviceRelation({k: self.columns[k] for k in names},
                              valid=self.valid)

    # -- the single host-materialization point -----------------------------
    def to_host(self) -> Relation:
        """Materialize to a host Relation with ONE batched device→host fetch."""
        forced = {k: c.force() for k, c in self.columns.items()}
        # device_get rebuilds the dict with sorted keys: the result keeps
        # this relation's column order instead
        if self.valid is not None:
            payload = jax.device_get((forced, self.valid))
            cols, valid = payload
            keep = np.nonzero(np.asarray(valid))[0]
            return Relation({k: np.asarray(cols[k])[keep] for k in forced})
        cols = jax.device_get(forced)
        return Relation({k: np.asarray(cols[k]) for k in forced})
