"""serve.queue_wait_ms: the 95th percentile, nearest rank, of
ServeResult.queue_wait_s, the program's own span from submission to the
start of the dispatch, over the window's completed requests."""

from bench.harness.record import percentile


def read(run):
    if run.loop != "open":
        return None
    waits = [r.result.queue_wait_s for r in run.records
             if r.result is not None]
    p95 = percentile(waits, 95)
    return None if p95 is None else p95 * 1e3
