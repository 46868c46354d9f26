"""Selector and broker: programs run for the first time per served query.

Mean over the window's queries of ``fresh_programs`` in each query's
``QueryResult.trace`` (``repro.core.tracing``): dispatches, fused or per
operator, that ran a program the process had not run before, so paid a
compile or a load from the persistent cache.  0 once set-up has warmed
every shape.  No reading where the run's query records carry no trace.
Moves ``query_p95_s``.
"""


def read(run):
    traces = [getattr(q, "trace", None) for q in run.queries]
    if not traces or None in traces:
        return None
    return sum(t.fresh_programs for t in traces) / len(traces)
