"""serve_p95_ms: the 95th percentile, nearest rank, of every request due in
the window, each timed from its due time to its future's completion. A
request that failed or never came counts as slower than every other; where
those are more than 5% of the window, there is no p95 to give."""

from bench.harness.record import percentile


def read(run):
    if run.loop != "open" or not run.records:
        return None
    lat = [r.latency if r.error is None and r.latency is not None
           else float("inf") for r in run.records]
    p95 = percentile(lat, 95)
    return None if p95 == float("inf") else p95 * 1e3
