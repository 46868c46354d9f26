"""Kernels: the segment_join Pallas kernels' share of their roofline, in %.

Least time of the kernels' work over their summed device time in the
trace.  The work is the configuration's ``kernel_bytes`` for each traced
query (probe keys and build keys read, one match row per probe row
written) over the HBM peak of the device kind; the time is the summed
duration of the Pallas custom calls in the trace, which the TPU names
after the jitted wrapper of each kernel (``radix_partition``,
``radix_hash_probe``, ``segment_sum``).  A trace with no
such event, or a template with no kernel work, gives no reading.  Moves
``throughput_qps``.
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
    work = sum(run.config.kernel_bytes(t, run.table_rows) for t in tr.queries)
    if kernel_s <= 0 or work <= 0:
        return None
    return 100.0 * work / run.peak("hbm_bytes_per_s") / kernel_s
