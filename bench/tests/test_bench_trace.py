"""The reduction from a profiler trace to device numbers, on a synthetic
trace worked by hand and on a slice recorded on a TPU v5e."""

import pytest

from bench import trace
from bench.roofline import is_paged_kernel
from bench.spec import BENCH

MS = 1e6   # ns


def synthetic():
    # window 0..100 ms; a loop (10..50) holding two ops, a kernel 60..70,
    # an op straddling the window's end (95..110)
    ops = [["%while.1 while", 10 * MS, 40 * MS, "jit_step"],
           ["%fusion.1 fusion", 12 * MS, 20 * MS, "jit_step"],
           ["%fusion.2 fusion", 35 * MS, 10 * MS, "jit_step"],
           ["%k custom-call tpu_custom_call", 60 * MS, 10 * MS, "jit_decode"],
           ["%fusion.1 fusion", 95 * MS, 15 * MS, "jit_step"]]
    host = [["bench.window", 0.0, 100 * MS],
            ["bench.step", 5 * MS, 70 * MS],
            ["bench.wait", 75 * MS, 20 * MS]]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_busy_idle_and_self_time():
    r = trace.reduce(synthetic(), kernel=is_paged_kernel)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: 10..50, 60..70, 95..100
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["kernel_s"] == pytest.approx(0.010)
    assert r["kernel_calls"] == 1
    ops = dict(r["device_ops"])
    assert ops["jit_step %while.1 while"] == pytest.approx(0.010)  # 40-20-10
    assert ops["jit_step %fusion.1 fusion"] == pytest.approx(0.025)  # 20+5
    # idle gaps 0..10 (step), 50..60 (step), 70..95 (step 5, wait 20)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["wait", pytest.approx(0.025)]
    assert sorted(g[1] for g in gaps) == pytest.approx([0.01, 0.01, 0.025])
    assert r["idle_by_host"]["wait"] == pytest.approx(0.020)
    assert r["idle_by_host"]["step"] == pytest.approx(0.020)


def test_no_kernel_reads_zero_seconds():
    r = trace.reduce(synthetic(), kernel=lambda op, module: False)
    assert r["kernel_s"] == 0.0


def test_two_devices_average():
    t = synthetic()
    t["devices"]["/device:TPU:1"] = [["%x copy", 0.0, 100 * MS, "jit_step"]]
    r = trace.reduce(t)
    assert r["busy_s"] == pytest.approx((0.055 + 0.1) / 2)


def test_short_names_from_hlo():
    op = ('%closed_call.29 = bf16[32,8,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} '
          'custom-call(s32[32,161]{1,0:T(8,128)S(1)} %copy-done), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          '{s32[32,161]{1,0}}')
    assert trace.short_op(op) == "%closed_call.29 custom-call tpu_custom_call"
    loop = ("%while.41 = (s32[]{:T(128)}, bf16[6,8,4096,16,128]{4,1,3,2,0:"
            "T(8,128)(2,1)}) while((s32[]{:T(128)}, bf16[6]) %tuple.102), "
            "condition=%c, body=%b")
    assert trace.short_op(loop) == "%while.41 while"
    assert trace.short_module("jit_decode_chunk_paged(2287199426)") == (
        "jit_decode_chunk_paged")


def test_ops_take_the_module_that_holds_them():
    ops = [("%a = f32[] add(f32[] %x)", 5.0, 1.0),
           ("%b = f32[] add(f32[] %x)", 15.0, 1.0),
           ("%c = f32[] add(f32[] %x)", 30.0, 1.0)]
    mods = [(0.0, 10.0, "jit_one"), (12.0, 20.0, "jit_two")]
    got = trace._attribute(ops, mods)
    assert [e[3] for e in got] == ["jit_one", "jit_two", ""]
    assert got[0][0] == "%a add"


def test_recorded_trace_against_a_raster():
    """0.7 s of a deepseek67b.chat window recorded on a TPU v5e: the
    union of op intervals agrees with a 100 ns raster of the same events,
    and the kernel is the one Pallas call, found only in the paged decode
    program."""
    import numpy as np
    t = trace.read(BENCH / "tests" / "data" / "trace_chat_slice.json.gz")
    r = trace.reduce(t, kernel=is_paged_kernel)
    lo, hi = trace.window(t)
    (evs,) = t["devices"].values()
    grid = np.zeros(int((hi - lo) / 100) + 1, bool)
    for _, s, d, _ in evs:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) / 100):int((b - lo) / 100)] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-7, rel=1e-3)
    assert 0 < r["kernel_s"] < r["busy_s"] <= r["window_s"]
    kernels = [e for e in evs if is_paged_kernel(e[0], e[3])]
    assert {e[3] for e in kernels} == {"jit_decode_chunk_paged"}
    assert all(e[0].endswith("tpu_custom_call") for e in kernels)
    assert sum(e[0].endswith("tpu_custom_call") for e in evs) == len(kernels)
    idle = r["window_s"] - r["busy_s"]
    assert sum(g for _, g in r["idle_gaps"]) <= idle + 1e-9
