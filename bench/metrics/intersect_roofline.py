"""intersect_roofline: the share of the HBM roofline that the counts reach.

The least time a count can take is the bytes a merge intersection of the
oriented int32 adjacency lists must read (``work["merge_bytes"]``, computed
from the graph alone by ``bench.harness.reference.merge_bytes``) over the
chip's HBM bandwidth from ``bench/peaks.json``. It is divided by the device
busy time per count in the traced window. Counting is memory-bound, and the
v5e publishes no int32 vector peak, so no compute bound enters.
"""


def read(run):
    if run.trace is None or run.loop != "closed" or not run.records \
            or run.trace.busy_s <= 0:
        return None
    least = run.work["merge_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (run.trace.busy_s / len(run.records))
