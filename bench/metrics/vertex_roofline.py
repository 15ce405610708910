"""vertex_roofline: the share of the HBM roofline that the per-vertex passes
reach.

The least time a pass can take is the bytes it must move
(``work["vertex_bytes"]``: the merge intersection's reads plus the (n,)
int32 counts written once, ``bench.harness.vertex_reference.vertex_bytes``)
over the chip's HBM bandwidth from ``bench/peaks.json``. It is divided by
the device busy time per pass in the traced window. The scatter's updates
cost no bytes, since a kernel may keep the counts on chip, and no compute
bound enters: the v5e publishes no int32 vector peak.
"""


def read(run):
    if run.trace is None or run.loop != "closed" or not run.records \
            or run.trace.busy_s <= 0 or "vertex_bytes" not in run.work:
        return None
    least = run.work["vertex_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (run.trace.busy_s / len(run.records))
