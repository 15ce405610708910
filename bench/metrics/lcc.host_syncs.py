"""lcc.host_syncs: device results the host blocked on per per-vertex pass in
the window: the increase of the program's ``tc.host_syncs`` counter over the
window, over the passes (``tc.vertex_passes``) completed in it."""

from bench.harness.passes import per_pass


def read(run):
    return per_pass(run, "tc.host_syncs")
