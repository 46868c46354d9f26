"""Fused fragments: device time of the join cores per traced query, in ms.

Sum over the device scopes whose name starts with ``join.`` (the sorted,
dense and dictionary cores, their prefix sums and expansions) of each
scope's device seconds, the union of its op intervals, over the traced
queries.  Reads the trace summary's ``scope_seconds`` (``bench/scopes.py``
computes it); no reading where the summary has none.  Moves
``throughput_qps``.
"""


def read(run):
    tr = run.trace
    seconds = getattr(tr, "scope_seconds", None)
    if tr is None or not seconds or not tr.queries:
        return None
    return 1e3 * sum(s for scope, s in seconds.items()
                     if scope.startswith("join.")) / len(tr.queries)
