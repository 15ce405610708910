"""setup.plan_s: host seconds of the plan (core.engine.plan_triangle_count
and core.prep: orientation, buckets, upload), ended by block_until_ready on
the plan's resident buffers."""


def read(run):
    return run.spans.get("plan_s")
