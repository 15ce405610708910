"""serve.coalesce: requests per device dispatch, over the window's completed
requests: their number over the sum of 1 / ServeResult.batch_size."""


def read(run):
    done = [r.result for r in run.records if r.result is not None]
    if run.loop != "open" or not done:
        return None
    return len(done) / sum(1.0 / r.batch_size for r in done)
