"""lcc.dispatches: device calls the host enqueued per per-vertex pass in the
window: the increase of the program's ``tc.dispatches`` counter over the
window, over the passes (``tc.vertex_passes``) completed in it (resident
stages plus streamed chunks)."""

from bench.harness.passes import per_pass


def read(run):
    return per_pass(run, "tc.dispatches")
