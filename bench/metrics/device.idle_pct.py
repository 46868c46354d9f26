"""Device: share of the traced window in which no op ran on the device, in %.

1 - (union of the op intervals on the TPU device planes) / (the traced
window, from the first query span's start to the last one's end).
Moves ``throughput_qps``.
"""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * tr.idle_share
