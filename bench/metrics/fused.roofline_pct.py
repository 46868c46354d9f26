"""Fused fragments: least time of the traced queries' work over device busy time, in %.

The least time of a query is the bytes of every base-table column its
template reads, once at logical width, plus its result, over the HBM
peak of the device kind; the configuration's ``query_bytes`` computes the
bytes from table sizes and the template alone.  Busy time is the union of
the device's op intervals over the traced window.  Moves
``throughput_qps``.
"""


def read(run):
    tr = run.trace
    if tr is None or not tr.queries or tr.busy_s <= 0:
        return None
    least_s = sum(run.config.query_bytes(t, run.table_rows)
                  for t in tr.queries) / run.peak("hbm_bytes_per_s")
    return 100.0 * least_s / tr.busy_s
