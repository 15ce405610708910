"""The trace reduction: busy union, idle share and the naming of idle gaps,
on hand-made intervals and on a small profile recorded here."""

import time

import pytest

from bench.harness import trace


def test_union_merges_overlaps_and_touching():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == [
        (0, 4), (5, 7), (9, 10)]
    assert trace.union([]) == []


def test_attribute_picks_innermost_open_event():
    host = [("bench.window", 0, 100), ("bench.count", 10, 50),
            ("$engine.py:1 run", 20, 30)]
    assert trace.attribute([5, 25, 40, 60, 150], host) == [
        "bench.window", "$engine.py:1 run", "bench.count", "bench.window",
        trace.NO_SPAN]


def test_summarize_busy_idle_and_gaps():
    raw = trace.RawTrace(
        device_ops={"/device:TPU:0": [("fusion.1", 10, 30), ("fusion.1", 25,
                                                             40),
                                      ("copy", 60, 70), ("copy", 95, 120)]},
        host=[("bench.window", 0, 100), ("bench.count", 0, 50),
              ("bench.wait", 50, 100)])
    s = trace.summarize(raw)
    assert s.window_s == pytest.approx(100e-9)
    # busy inside [0, 100): [10, 40) + [60, 70) + [95, 100) = 45 ns
    assert s.busy_s == pytest.approx(45e-9)
    # gaps: [0,10) count, [40,60) midpoint 50 -> wait, [70,95) wait
    assert dict(s.idle_gaps) == pytest.approx({"bench.count": 10e-9,
                                               "bench.wait": 45e-9})
    assert s.longest_gaps[0] == ("bench.wait", pytest.approx(25e-9))
    assert dict(s.device_ops) == pytest.approx({"fusion.1": 35e-9,
                                                "copy": 15e-9})


def test_summarize_averages_busy_over_devices():
    raw = trace.RawTrace(
        device_ops={"/device:TPU:0": [("a", 0, 50)],
                    "/device:TPU:1": [("a", 0, 100)]},
        host=[("bench.window", 0, 100)])
    s = trace.summarize(raw)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(75e-9)


def test_recorded_profile(tmp_path):
    """A real profile on this backend: the window and count spans are found,
    the ops inside them are busy time, and the sleep is an idle gap named by
    the span around it."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.count"):
                    f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.05)
    raw = trace.load(str(tmp_path))
    assert raw.device_ops, "no device operations found in the profile"
    names = {h[0] for h in raw.host}
    assert {"bench.window", "bench.count", "bench.wait"} <= names
    s = trace.summarize(raw)
    assert 0.05 <= s.window_s < 5.0
    assert 0.0 < s.busy_s < s.window_s
    gaps = dict(s.idle_gaps)
    assert gaps["bench.wait"] >= 0.045
    assert max(gaps, key=gaps.get) == "bench.wait"
