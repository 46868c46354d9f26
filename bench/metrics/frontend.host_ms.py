"""Front end: host time per query outside the engine's operators, in ms.

Mean over the window's queries of the client-side latency minus the sum of
the query's ``OpMetrics.wall_s`` and ``mem_wait_s``: planning, path
selection and result assembly on the host.  Moves ``query_p50_s``.
"""


def read(run):
    qs = run.queries
    if not qs:
        return None
    return 1e3 * sum(q.latency_s - q.op_wall_s - q.mem_wait_s
                     for q in qs) / len(qs)
