"""The benchmark's trace reduction and its table of peaks.

The reduction is checked twice: on events made up here, whose busy time,
idle gaps and kinds can be worked out by hand, and on a small trace that
the profiler records on the CPU.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import peaks, trace  # noqa: E402

MS = 1_000_000  # ns


def test_reduce_by_hand():
    host = [("window", 0, 100 * MS), ("chunk_dispatch", 0, 5 * MS),
            ("device_wait", 5 * MS, 90 * MS), ("history_fetch", 90 * MS,
                                               100 * MS),
            ("setup", -50 * MS, 0)]
    kernel = "%closed_call.1 = (f32[1,8]{1,0}) custom-call(s32[8]{0} %a.1)"
    ops = [("%while.9 = (s32[]) while((s32[]) %t), body=%b", 10 * MS, 80 * MS),
           (kernel, 10 * MS, 30 * MS),  # inside the loop
           ("%sort.2 = (s32[4]{0:T(128)}, f32[4]) sort(s32[4] %x)", 30 * MS,
            40 * MS),
           ("%fusion.3 = f32[8] fusion(f32[8] %custom-call.7)", 50 * MS,
            80 * MS),
           ("%fusion.3 = f32[8] fusion(f32[8] %custom-call.7)", 95 * MS,
            120 * MS),  # clipped at 100
           ("fusion.4", -20 * MS, -10 * MS)]  # before the window
    r = trace.reduce({"/device:TPU:0": ops}, host)
    assert r["window_s"] == pytest.approx(0.100)
    # union: [10, 80) + [95, 100)
    assert r["busy_s"] == pytest.approx(0.075)
    # the loop's own time is what its body leaves: 70 - 20 - 10 - 30
    assert r["kind_s"] == pytest.approx(
        {"kernel": 0.020, "sort": 0.010, "other": 0.045})
    assert r["device_ops"][0] == ["%fusion.3 (fusion)", pytest.approx(0.035)]
    assert len(r["device_ops"]) == 4
    # gaps [0, 10), [80, 95), longest first, each named by the span that
    # overlaps it most ([0, 10) is a tie: the first span wins)
    assert r["idle_gaps"] == [["device_wait", pytest.approx(0.015)],
                              ["chunk_dispatch", pytest.approx(0.010)]]


def test_reduce_averages_over_devices():
    host = [("window", 0, 10 * MS)]
    devs = {"/device:TPU:0": [("a", 0, 10 * MS)],
            "/device:TPU:1": [("a", 0, 5 * MS)]}
    r = trace.reduce(devs, host)
    assert r["busy_s"] == pytest.approx(0.0075)
    assert r["kind_s"]["other"] == pytest.approx(0.0075)
    assert r["idle_gaps"] == [["host", pytest.approx(0.005)]]


def test_reduce_refuses_empty():
    with pytest.raises(ValueError, match="window"):
        trace.reduce({"d": [("a", 0, 1)]}, [("setup", 0, 1)])
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce({}, [("window", 0, 1)])


def test_kind():
    assert trace.kind("%closed_call.12 = (f32[1,8]{1,0:T(1,128)}, s32[1,8]) "
                      "custom-call(s32[8]{0:T(1024)S(1)} %b)") == "kernel"
    assert trace.kind("%sort.41 = (s32[6]{0:T(1024)}, f32[6]{0:T(1024)}) "
                      "sort(s32[6]{0:T(1024)} %g)") == "sort"
    # an operand named after a custom call or a sort does not count
    assert trace.kind("%fusion.209 = f32[2]{0:T(1024)} fusion(f32[4,6] "
                      "%copy-done.3, s32[2] %custom-call.77), kind=kCustom"
                      ) == "other"
    assert trace.kind("%fusion.9 = f32[2] fusion(f32[2] %sort.38)") == "other"
    assert trace.kind("sort.26") == "sort"
    assert trace.kind("custom-call.3") == "kernel"
    assert trace.kind("cond.44") == "other"
    assert trace.short("%sort.41 = (s32[6], f32[6]) sort(s32[6] %g)") == \
        "%sort.41 (sort)"


def test_self_times():
    ops = [("loop", 0, 100), ("a", 10, 30), ("b", 30, 50), ("c", 60, 70),
           ("d", 200, 210)]
    own = {n: t for n, _, _, t in trace.self_times(ops)}
    assert own == {"loop": 50, "a": 20, "b": 20, "c": 10, "d": 10}


def test_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax import profiler

    f = jax.jit(lambda x: jnp.sort(jnp.sin(x) @ x.T, axis=-1).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    profiler.start_trace(str(tmp_path))
    try:
        with profiler.TraceAnnotation("window"):
            for _ in range(3):
                with profiler.TraceAnnotation("chunk_dispatch"):
                    y = f(x)
                with profiler.TraceAnnotation("device_wait"):
                    y.block_until_ready()
    finally:
        profiler.stop_trace()
    r = trace.summarize(str(tmp_path))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["kind_s"].get("sort", 0) > 0
    assert 0 < len(r["device_ops"]) <= 10
    assert len(r["idle_gaps"]) <= 10
    assert {name for name, _ in r["idle_gaps"]} <= set(trace.HOST_SPANS) | {
        "host"}


def test_peaks():
    p = peaks.lookup("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v9 imaginary")
