"""Kernels: device time of the segment_join Pallas kernels per traced
query, in ms.

The summed duration of the Pallas custom calls in the trace, by the same
test as ``kernels.segment_join_roofline`` (the TPU names each call after
the jitted wrapper of its kernel: ``radix_partition``,
``radix_hash_probe``, ``segment_sum``), over the number of traced
queries.  A trace with no such event gives no reading.  Moves
``query_p50_s``.
"""

#: the wrappers of the Pallas kernels of ``kernels/segment_join``
KERNELS = ("radix_partition", "radix_hash_probe", "segment_sum")


def is_kernel(op: str) -> bool:
    return op.endswith("tpu_custom_call") and op.startswith(KERNELS)


def read(run):
    tr = run.trace
    if tr is None or not tr.queries:
        return None
    kernel_s = tr.seconds_where(is_kernel)
    if kernel_s <= 0:
        return None
    return 1e3 * kernel_s / len(tr.queries)
