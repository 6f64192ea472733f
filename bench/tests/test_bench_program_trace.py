"""The readers of the program's own spans: on a synthetic trace worked by
hand, and on a traced run of the harness on the CPU at a tiny size."""

import json
from types import SimpleNamespace

import pytest

from bench import program_trace, run, serve as serve_mod, spec, trace
from bench.spec import BENCH
from bench.tests.test_bench_harness import cell, serve

MS = 1e6   # ns


def synthetic():
    """Window 0..100 ms, three steps. Device busy 12-30, 42-60 (decode),
    72-80 (prefill), 85-95, 96-104 (decode); idle 0-12, 30-42, 60-72,
    80-85, 95-96. The host is in no program span 0-5, waits on the decode
    60-61, and dispatches the last chunk before reading the one before it
    back (95-96, then 96-97)."""
    with open(BENCH / "tests" / "data" / "program_trace_synthetic.json") as f:
        return json.load(f)


def test_idle_put_down_to_innermost_span():
    got = program_trace.idle_by_span(synthetic())
    want = {"repro.decode.alloc": 5, "repro.decode.dispatch": 7,
            "repro.decode.readback": 2, "repro.decode.walk": 11,
            "repro.write_state": 2, "repro.observe": 1, "repro.step": 3,
            "repro.admit": 0.5, "repro.prefill.dispatch": 1.5,
            "repro.prefill.insert": 3, "repro.decode.wait": 1}
    assert got == pytest.approx({k: v * 1e-3 for k, v in want.items()})


def test_host_idle_leaves_out_waits_and_the_harness():
    t = synthetic()
    share = program_trace.host_idle_share(t)
    assert share == pytest.approx(36.0)
    # the device's idle 42%: 5 ms outside every span, 1 ms in decode.wait
    r = trace.reduce(t)
    device_idle = 100.0 * (1 - r["busy_s"] / r["window_s"])
    assert device_idle == pytest.approx(42.0)
    assert share <= device_idle


def test_decode_turnaround_pairs_skip_prefills_and_clamp_at_zero():
    t = synthetic()
    # 8 -> 41: read-back ends 31, 10 ms; 41 -> 84: a prefill starts at
    # 70.5, skipped; 84 -> 95: dispatched before the read-back ended, 0
    assert program_trace.decode_turnarounds_ms(t) == pytest.approx(
        [10.0, 0.0])
    assert program_trace.decode_turnaround_ms(t) == pytest.approx(5.0)


def test_pairs_outside_the_window_are_left_out():
    t = synthetic()
    t["host"][0] = ["bench.window", 20 * MS, 80 * MS]     # 20..100 ms
    assert program_trace.decode_turnarounds_ms(t) == pytest.approx([0.0])
    t["program"] = [e for e in t["program"]
                    if e[0] != "repro.decode.dispatch" or e[1] < 90 * MS]
    assert program_trace.decode_turnaround_ms(t) is None


def test_no_device_counts_the_whole_window_idle():
    t = synthetic()
    t["devices"] = {}
    idle = program_trace.idle_by_span(t)
    # every span's own time inside the window: 0-5 and 99-100 are outside
    assert sum(idle.values()) == pytest.approx(0.094)


@pytest.mark.parametrize("name,want", [
    ("host_idle_share.open", 36.0), ("host_idle_share.closed", 36.0),
    ("decode_turnaround_ms.open", 5.0), ("decode_turnaround_ms.closed", 5.0)])
def test_readers(name, want, monkeypatch):
    read = spec.metric_reader(name)
    monkeypatch.setattr(program_trace, "load", lambda: synthetic())
    assert read(SimpleNamespace(trace={})) == pytest.approx(want)
    # an untraced run reads nothing
    assert read(SimpleNamespace(trace=None)) is None


def test_a_trace_without_program_spans_reads_nothing(tmp_path):
    assert program_trace.load(tmp_path) is None
    import jax
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        pass
    jax.profiler.stop_trace()
    assert program_trace.load(tmp_path) is None


def test_traced_run_reads_program_spans(tmp_path, monkeypatch, capsys):
    """A traced closed-loop run of the tiny program: both closed-loop
    readers find the program's spans, and the host's share of the idle
    is at most the device's."""
    # its own trace and runtime: the harness's tests may run beside it
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(serve_mod, "RUNTIME_ROOT", tmp_path / "runtime")
    monkeypatch.setattr(program_trace, "TRACE_DIR", tmp_path / "trace")
    res = serve(cell("tiny_ln", "tiny_agent"), tmp_path, monkeypatch, capsys,
                trace=1)
    assert res["correct"] is True
    m = res["metrics"]
    assert 0 < m["host_idle_share.closed"]["value"] <= (
        m["device_idle_share.closed"]["value"])
    assert m["decode_turnaround_ms.closed"]["value"] >= 0
    t = program_trace.load(tmp_path / "trace")
    names = {e[0] for e in t["program"]}
    assert {"repro.step", "repro.admit", "repro.prefill", "repro.decode",
            "repro.decode.walk"} <= names
