"""Fused fragments: host time per query around the fused program, in ms.

Mean over the window's queries of the seconds of the engine's
``rel.host_prep`` span (host planning, device layouts, any upload, the
program lookup) and ``rel.assemble`` span (the result built from the
fetched arrays), as each query's ``QueryResult.trace`` records them
(``repro.core.tracing``).  No reading where the run's query records carry
no trace.  Moves ``query_p50_s``.
"""


def read(run):
    traces = [getattr(q, "trace", None) for q in run.queries]
    if not traces or None in traces:
        return None
    return 1e3 * sum(t.seconds.get("rel.host_prep", 0.0)
                     + t.seconds.get("rel.assemble", 0.0)
                     for t in traces) / len(traces)
