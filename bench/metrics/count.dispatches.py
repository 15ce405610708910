"""count.dispatches: device calls the host enqueued per count in the
window: the increase of the program's ``tc.dispatches`` counter over the
window, over the counts completed in it (resident stages plus streamed
chunks)."""

from bench.harness.program import per_count


def read(run):
    return per_count(run, "tc.dispatches")
