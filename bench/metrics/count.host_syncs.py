"""count.host_syncs: device results the host blocked on per count in the
window: the increase of the program's ``tc.host_syncs`` counter over the
window, over the counts completed in it."""

from bench.harness.program import per_count


def read(run):
    return per_count(run, "tc.host_syncs")
