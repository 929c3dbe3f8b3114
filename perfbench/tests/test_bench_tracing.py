"""Reduction of a trace to busy time, idle gaps and top device ops."""
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench import tracing


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (38, 45)]
    assert tracing.union_seconds(iv) == pytest.approx(35e-9)
    assert tracing.gaps(iv, 0, 50) == [(20, 30), (45, 50)]
    assert tracing.gaps([], 3, 9) == [(3, 9)]


def test_summary_of_a_synthetic_trace():
    ops = [[("matmul", 100, 300), ("matmul", 500, 600), ("gather", 250, 400),
            ("late", 950, 1200)]]
    host = [("bench.query", 0, 1000), ("bench.plan", 0, 120),
            ("bench.backend", 600, 900)]
    s = tracing.summarize(ops, host, 0, 1000)
    assert s.chips == 1
    assert s.window_s == pytest.approx(1e-6)
    # busy: 100-400, 500-600, 950-1000
    assert s.busy_s == pytest.approx(450e-9)
    # every op's time inside the window, by name
    assert sum(d for _, d in s.device_ops) == pytest.approx(
        (200 + 100 + 150 + 50) * 1e-9)
    assert s.device_ops[0] == ["matmul", pytest.approx(300e-9)]
    # longest gaps first, each named by the innermost open span
    assert s.idle_gaps == [["bench.backend", pytest.approx(350e-9)],
                           ["bench.plan", pytest.approx(100e-9)],
                           ["bench.query", pytest.approx(100e-9)]]


def test_no_device_plane_reads_as_no_chip():
    s = tracing.summarize([], [("bench.query", 0, 10)], 0, 10)
    assert s.chips == 0 and s.busy_s == 0


def test_spans_on_a_recorded_trace(tmp_path):
    spans = tracing.Spans()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.span("bench.window"):
            with spans.span("bench.query"):
                f(x).block_until_ready()
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    assert [n for n, _, _ in spans.items] == ["bench.query", "bench.window"]
    assert spans.seconds("bench.query") >= 0.01
    s = tracing.read_trace(str(tmp_path), "bench.window")
    # the CPU has no TPU plane: the window is found, no chip is read
    assert s.window_s >= 0.01 and s.chips == 0
