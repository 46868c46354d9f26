"""Selector and broker: share of query latency spent waiting, in %.

Sum over the window's queries of ``queue_wait_s`` (device lease) and
``mem_wait_s`` (memory admission), over the sum of their latencies.
Moves ``query_p95_s``.
"""


def read(run):
    total = sum(q.latency_s for q in run.queries)
    if total <= 0:
        return None
    return 100.0 * sum(q.queue_wait_s + q.mem_wait_s
                       for q in run.queries) / total
