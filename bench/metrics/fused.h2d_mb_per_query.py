"""Fused fragments: host-to-device megabytes (1e6 bytes) per completed query.

Sum of the physical ``OpMetrics.h2d_bytes`` over the window's queries, per
query.  Base tables are device-resident after set-up, so this is the
traffic of intermediate results that chained fragments upload again.
Moves ``query_p50_s``.
"""


def read(run):
    if not run.queries:
        return None
    return sum(q.h2d_bytes for q in run.queries) / len(run.queries) / 1e6
