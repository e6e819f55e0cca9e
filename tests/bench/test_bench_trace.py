"""The reduction from a trace to busy time, idle share, kernel time and
idle gaps by host activity."""
import json
import os

import numpy as np
import pytest

from harness import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_small.json")
MS = 1_000_000          # ns
A = "%_packed_codebook_matmul_jit"
A_TEXT = " = f32[8,64] custom-call(f32[8,64] %x, u32[8,64] %w)"
B = "%_paged_attention_jit"


def _made():
    """A 10 ms window on one chip: ops at 1-3 (kernel A), 2-4 (fusion,
    overlapping), 6-7 (kernel B) and 9.5-11 (kernel A, cut by the
    window's end); the host was stepping over 0-4.5 and waiting over
    4.5-10."""
    dev = "/device:TPU:0"
    return {
        "device": [
            [dev, A + ".1" + A_TEXT, A + ".1", 1 * MS, 2 * MS],
            [dev, "%fusion.1 = f32[8] fusion(%" + B[1:] + ".2)",
             "fusion.1", 2 * MS, 2 * MS],
            [dev, B + ".2 = f32[8] custom-call()", B, 6 * MS, 1 * MS],
            [dev, A + ".3" + A_TEXT, A + ".3", 9.5 * MS, 1.5 * MS],
        ],
        "host": [["bench_window", 0.0, 10 * MS],
                 ["bench_step", 0.0, 4.5 * MS],
                 ["bench_wait", 4.5 * MS, 5.5 * MS]],
    }


def test_busy_is_the_union_of_op_intervals():
    b = trace.busy(_made())
    assert b["window_s"] == pytest.approx(0.010)
    assert b["busy_s"] == pytest.approx(0.0045)    # 1-4, 6-7, 9.5-10


def test_kernel_seconds_by_pattern():
    t = _made()
    assert trace.kernel_seconds(t, [r"_packed_codebook_matmul_jit"]) == \
        pytest.approx(0.0025)
    assert trace.kernel_seconds(t, [r"_paged_attention_jit", "fusion"]) == \
        pytest.approx(0.003)
    # an operand's name in the instruction text does not count
    assert trace.kernel_seconds(t, [r"_paged_attention_jit"]) == \
        pytest.approx(0.001)
    assert trace.kernel_seconds(t, ["nothing"]) == 0.0


def test_idle_gaps_by_host_span():
    gaps = dict(trace.idle_gaps(_made()))
    assert gaps["bench_step"] == pytest.approx(0.001)   # 0-1
    assert gaps["bench_wait"] == pytest.approx(0.0045)  # 4-6, 7-9.5


def test_top_ops_sum_to_busy_without_overlap():
    ops = dict(trace.top_ops(_made()))
    assert ops == pytest.approx({"_packed_codebook_matmul_jit": 0.0025,
                                 "fusion": 0.002,
                                 "_paged_attention_jit": 0.001})


def test_no_window_span_is_an_error():
    t = _made()
    t["host"] = t["host"][1:]
    with pytest.raises(RuntimeError):
        trace.busy(t)


def test_recorded_trace():
    """25 ms of a MiniCPM decode step on one v5e (the first seconds of a
    traced window, op text cut short), checked against a plain
    rasterization of the same events at 1 us."""
    with open(FIXTURE) as f:
        t = json.load(f)
    w0, w1 = trace.window(t)
    us = np.zeros(int((w1 - w0) / 1000) + 1, bool)
    packed = 0.0
    for _, name, _, start, dur in t["device"]:
        s, e = max(start, w0), min(start + dur, w1)
        if e <= s:
            continue
        us[int((s - w0) / 1000):int(np.ceil((e - w0) / 1000))] = True
        if name.startswith("%_packed_codebook_matmul_jit."):
            packed += (e - s) * 1e-9
    b = trace.busy(t)
    assert b["window_s"] == pytest.approx(0.025)
    assert b["busy_s"] == pytest.approx(us.sum() * 1e-6, abs=2e-5)
    assert trace.kernel_seconds(t, [r"_packed_codebook_matmul_jit"]) == \
        pytest.approx(packed)
    assert packed > 0
    s = trace.summary(t)
    idle = sum(v for _, v in s["idle_gaps"])
    assert idle <= s["window_s"] - s["busy_s"] + 1e-9
    assert all(name != "while" for name, _ in s["device_ops"])
