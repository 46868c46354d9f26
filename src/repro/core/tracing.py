"""Spans, device scopes and per-query counters: where a query's time goes.

One system with three parts, all always on:

* :class:`span` — a named host phase.  It opens a
  ``jax.profiler.TraceAnnotation``, so a running profiler records it on the
  host plane of the same trace as the device ops (on the clock the profiler
  aligns for both), and it adds its ``perf_counter`` duration to the calling
  thread's :class:`QueryTrace`.  With no profiler running an annotation
  costs about a microsecond.
* ``jax.named_scope`` around traced device code names the ``op_name``
  metadata of the HLO ops it emits (``jit(program)/join.sorted.search/...``)
  and changes no instruction.
* :class:`QueryTrace` — seconds by span name plus the dispatch counters of
  one query, attached to its ``QueryResult.trace``.

Span names all start with ``rel.``:

==================  ======================================================
``rel.query``       the query, at ``Session.execute`` / ``Executor.execute``
``rel.plan``        ``plan_program``: rewrites and fragment chaining
``rel.select``      broker quotes and the path selector's decision
``rel.host_prep``   fused host planning, device layouts, program lookup
``rel.h2d``         a host→device upload of table columns (cold only)
``rel.lease_wait``  waiting for a device lease from the broker
``rel.compile``     the first call of a fresh program (compiles, then runs)
``rel.dispatch``    the call of a warm fused program (asynchronous enqueue)
``rel.fetch``       ``jax.device_get`` of the fused result (waits for it)
``rel.assemble``    building the result from the fetched arrays
``rel.op.<op>``     one operator of the generic walk, or its root fetch
==================  ======================================================

Device scope names: ``join.sorted.sort``, ``join.sorted.search``,
``join.prefix_sum``, ``join.expand``, ``join.dense.build``,
``join.dense.probe``, ``join.dense.pallas``, ``join.dict.remap``,
``decode``, ``filter``, ``sort``, ``aggregate``, ``gather`` (fused
programs) and ``op.join``, ``op.join_aggregate``, ``op.sort``,
``op.group_by`` (per-operator programs).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, Iterator, Optional

import jax

__all__ = ["QueryTrace", "span", "query", "current", "count"]

_TLS = threading.local()


@dataclasses.dataclass
class QueryTrace:
    """What one query spent, by host span, and what it dispatched.

    ``seconds`` sums each span name's durations; spans nest (``rel.h2d``
    inside ``rel.host_prep``, everything inside ``rel.query``), so sums of
    different names may overlap.  ``fresh_programs`` counts dispatches that
    ran a program for the first time (a compile, or a load from the
    persistent cache); ``retries`` the fused re-runs after a capacity
    overflow or a duplicate-key guess; ``dispatches`` every device program
    dispatch, fused or per operator; ``probe_order_joins`` the fused
    dispatches whose join core kept its rows in probe order because the
    build keys were believed unique (read with ``retries``: a wrong belief
    costs one retry on the expanding core)."""

    seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    fresh_programs: int = 0
    retries: int = 0
    dispatches: int = 0
    probe_order_joins: int = 0

    def ms(self, *names: str) -> float:
        """Milliseconds summed over the spans ``names``."""
        return 1e3 * sum(self.seconds.get(n, 0.0) for n in names)


def current() -> Optional[QueryTrace]:
    """The calling thread's open query record, or None."""
    return getattr(_TLS, "trace", None)


@contextlib.contextmanager
def query() -> Iterator[QueryTrace]:
    """Open the calling thread's query record under a ``rel.query`` span.
    Nested opens (each planner stage re-enters the executor) share the
    outer record and open no second span."""
    outer = current()
    if outer is not None:
        yield outer
        return
    trace = _TLS.trace = QueryTrace()
    try:
        with span("rel.query"):
            yield trace
    finally:
        _TLS.trace = None


def count(fresh_programs: int = 0, retries: int = 0,
          dispatches: int = 0, probe_order_joins: int = 0) -> None:
    """Add to the calling thread's query counters (no-op outside a query)."""
    trace = current()
    if trace is not None:
        trace.fresh_programs += fresh_programs
        trace.retries += retries
        trace.dispatches += dispatches
        trace.probe_order_joins += probe_order_joins


class span:
    """Host span ``name``: a profiler annotation plus the query's seconds."""

    __slots__ = ("name", "_trace", "_annotation", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self._trace = current()
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        elapsed = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self._trace is not None:
            seconds = self._trace.seconds
            seconds[self.name] = seconds.get(self.name, 0.0) + elapsed
