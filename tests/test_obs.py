"""Observability layer: metrics registry + histogram/nearest-rank
agreement, span lifecycle invariants, the span-log -> registry recompute
(bitwise determinism), Chrome trace export/validation, tokens_wasted, and
the `repro top` / `ps` rendering."""

import io
import json
from contextlib import redirect_stdout
from types import SimpleNamespace

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.orchestrator.obs import (
    Histogram,
    MetricsRegistry,
    TraceBuffer,
    completion_snapshot,
    decomposition,
    export_chrome,
    itl_milliticks,
    merge_snapshots,
    recompute_registry,
    snapshot_exemplar,
    snapshot_percentile,
    snapshot_total,
    validate_chrome_trace,
    validate_span_log,
)
from repro.orchestrator.telemetry import latency_summary, nearest_rank

# ---------------------------------------------------------------------------
# histogram vs nearest_rank (satellite: property test)
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=4000),
                min_size=1, max_size=200),
       st.sampled_from([1, 2, 7, 50]),
       st.sampled_from([50, 99]))
def test_histogram_percentile_matches_nearest_rank(samples, width, pct):
    """The streaming histogram's percentile is nearest-rank by
    construction: EXACT for width 1 on integer samples, else within one
    bucket width below the true nearest-rank sample."""
    h = Histogram(width=width, n_buckets=4096)
    for s in samples:
        h.record(s)
    true = nearest_rank(samples, pct)
    got = h.percentile(pct)
    if width == 1:
        assert got == true
    else:
        assert got <= true < got + width


def test_histogram_percentile_matches_nearest_rank_fixed():
    """Deterministic replica of the property (runs even without
    hypothesis installed)."""
    rng = np.random.default_rng(7)
    for width in (1, 2, 7, 50):
        for _ in range(20):
            samples = rng.integers(0, 4000,
                                   int(rng.integers(1, 200))).tolist()
            h = Histogram(width=width, n_buckets=4096)
            for s in samples:
                h.record(s)
            for pct in (50, 99):
                true = nearest_rank(samples, pct)
                got = h.percentile(pct)
                assert got <= true < got + width
                if width == 1:
                    assert got == true


def test_histogram_empty_overflow_and_validation():
    h = Histogram(width=2, n_buckets=4)
    assert h.percentile(50) == 0 and h.count == 0
    h.record(1000)                       # clamps into the last bucket
    assert h.percentile(99) == (4 - 1) * 2
    with pytest.raises(ValueError):
        h.record(-1)
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        Histogram(width=0)
    with pytest.raises(ValueError):
        h.merge(Histogram(width=3, n_buckets=4))


def test_histogram_snapshot_roundtrip_and_merge():
    a, b = Histogram(width=2, n_buckets=8), Histogram(width=2, n_buckets=8)
    for v in (0, 3, 5, 9):
        a.record(v)
    for v in (1, 9):
        b.record(v)
    rt = Histogram.from_snapshot(a.snapshot())
    assert rt.counts == a.counts and rt.count == a.count and rt.sum == a.sum
    a.merge(b)
    assert a.count == 6 and a.sum == 0 + 3 + 5 + 9 + 1 + 9


# ---------------------------------------------------------------------------
# registry + snapshots
# ---------------------------------------------------------------------------


def test_registry_labels_totals_and_snapshot_determinism():
    r = MetricsRegistry()
    r.counter("tok", replica="r0").inc(3)
    r.counter("tok", replica="r1").inc(4)
    assert r.counter("tok", replica="r0") is r.counter("tok", replica="r0")
    assert r.total("tok") == 7
    r.gauge("depth").set(5)
    r.gauge("depth").set(2)
    assert r.gauge("depth").value == 2 and r.gauge("depth").high == 5
    with pytest.raises(ValueError):
        r.counter("neg").inc(-1)
    r.histogram("lat", width=1, n_buckets=16).record(3)
    with pytest.raises(ValueError):
        r.histogram("lat", width=2, n_buckets=16)       # geometry conflict
    assert json.dumps(r.snapshot()) == json.dumps(r.snapshot())


def test_merge_snapshots_and_snapshot_readers():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n").inc(2)
    b.counter("n").inc(5)
    a.gauge("g").set(3)
    b.gauge("g").set(4)
    a.histogram("h", width=1, n_buckets=8).record(2)
    b.histogram("h", width=1, n_buckets=8).record(6)
    m = merge_snapshots([a.snapshot(), b.snapshot()])
    assert snapshot_total(m, "n") == 7
    assert m["gauges"]["g"][""]["value"] == 7
    assert snapshot_percentile(m, "h", 99) == 6
    # absent/empty histograms read as None so renderers print '-'
    assert snapshot_percentile(m, "nope", 50) is None
    e = MetricsRegistry()
    e.histogram("h", width=1, n_buckets=8)
    assert snapshot_percentile(e.snapshot(), "h", 50) is None


def test_merge_snapshots_mismatched_labels_and_empty_pods():
    """The fleet rollup must tolerate pods that disagree on which label
    sets (and which metrics) exist, and pods that report nothing at all --
    a freshly-started replica snapshots as ``{}``-shaped sections."""
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("tok", replica="r0").inc(2)
    b.counter("tok", replica="r1").inc(5)          # disjoint label sets
    b.counter("only_b").inc(1)                     # metric a never saw
    a.gauge("depth", pod="p0").set(3)
    a.histogram("lat", width=1, n_buckets=8).record(4)
    m = merge_snapshots([a.snapshot(), {}, b.snapshot(),
                         MetricsRegistry().snapshot()])
    assert m["counters"]["tok"] == {"replica=r0": 2, "replica=r1": 5}
    assert snapshot_total(m, "tok") == 7
    assert snapshot_total(m, "only_b") == 1
    assert m["gauges"]["depth"]["pod=p0"]["value"] == 3
    assert snapshot_percentile(m, "lat", 99) == 4
    # order independence: the empty pods contribute nothing either way
    m2 = merge_snapshots([{}, b.snapshot(), a.snapshot()])
    assert json.dumps(m, sort_keys=True) == json.dumps(m2, sort_keys=True)


def test_merge_snapshots_geometry_mismatch_raises():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("lat", width=1, n_buckets=8).record(1)
    b.histogram("lat", width=2, n_buckets=8).record(1)
    with pytest.raises(ValueError, match="geometry"):
        merge_snapshots([a.snapshot(), b.snapshot()])


# ---------------------------------------------------------------------------
# exemplars: representative rid per histogram bucket
# ---------------------------------------------------------------------------


def test_exemplar_min_combine_is_order_independent():
    """Each bucket keeps the SMALLEST rid seen, so record order (and
    merge order) cannot perturb the snapshot -- the live-vs-recompute
    bitwise match depends on this."""
    h1 = Histogram(width=10, n_buckets=8)
    h2 = Histogram(width=10, n_buckets=8)
    for v, rid in [(5, 7), (5, 3), (25, 9)]:
        h1.record(v, exemplar=rid)
    for v, rid in [(25, 9), (5, 3), (5, 7)]:
        h2.record(v, exemplar=rid)
    assert h1.exemplars == h2.exemplars == {0: 3, 2: 9}
    assert h1.snapshot() == h2.snapshot()
    # merge min-combines too, in either direction
    m1 = Histogram(width=10, n_buckets=8)
    m1.record(5, exemplar=100)
    m1.merge(h1)
    m2 = Histogram(width=10, n_buckets=8)
    m2.merge(h1)
    m2.record(5, exemplar=100)
    assert m1.exemplars == m2.exemplars == {0: 3, 2: 9}


def test_exemplar_at_follows_nearest_rank_bucket():
    h = Histogram(width=1, n_buckets=64)
    for v in range(10):
        h.record(v, exemplar=1000 + v)
    assert h.exemplar_at(50) == 1004       # p50 -> sample 4's bucket
    assert h.exemplar_at(99) == 1009       # p99 -> the slowest sample
    assert Histogram(width=1, n_buckets=4).exemplar_at(99) is None
    # a bucket recorded without an exemplar reads as None, not garbage
    g = Histogram(width=1, n_buckets=4)
    g.record(2)
    assert g.percentile(99) == 2 and g.exemplar_at(99) is None


def test_exemplar_snapshot_roundtrip_and_legacy_snapshots():
    h = Histogram(width=2, n_buckets=8)
    h.record(3, exemplar=42)
    snap = h.snapshot()
    assert snap["exemplars"] == {"1": 42}
    rt = Histogram.from_snapshot(snap)
    assert rt.exemplars == {1: 42} and rt.snapshot() == snap
    # pre-exemplar state files lack the key entirely: still loadable
    legacy = dict(snap)
    del legacy["exemplars"]
    assert Histogram.from_snapshot(legacy).exemplars == {}


def test_snapshot_exemplar_merges_across_labels():
    r = MetricsRegistry()
    r.histogram("lat", width=1, n_buckets=32,
                replica="r0").record(4, exemplar=11)
    r.histogram("lat", width=1, n_buckets=32,
                replica="r1").record(20, exemplar=77)
    snap = r.snapshot()
    assert snapshot_percentile(snap, "lat", 99) == 20
    assert snapshot_exemplar(snap, "lat", 99) == 77
    assert snapshot_exemplar(snap, "lat", 50) == 11
    assert snapshot_exemplar(snap, "nope", 99) is None
    e = MetricsRegistry()
    e.histogram("lat", width=1, n_buckets=32)      # registered, no samples
    assert snapshot_exemplar(e.snapshot(), "lat", 99) is None


def test_latency_summary_carries_count():
    """nearest_rank returns 0 for empty input -- the count disambiguates a
    true 0-tick latency from 'no samples' (renderers print '-')."""
    assert latency_summary([]) == {"latency_count": 0,
                                   "p50_latency_ticks": 0,
                                   "p99_latency_ticks": 0}
    done = [SimpleNamespace(arrival=0, submit_tick=0, done_tick=t)
            for t in (4, 8)]
    s = latency_summary(done)
    assert s["latency_count"] == 2 and s["p99_latency_ticks"] == 8


def test_itl_milliticks_edges():
    assert itl_milliticks(0, 100, 1) == 0        # no inter-token gap exists
    assert itl_milliticks(0, 100, 0) == 0
    assert itl_milliticks(2, 10, 5) == 2000      # 8 ticks / 4 gaps
    assert itl_milliticks(0, 10, 4) == 3333      # floor, deterministic


# ---------------------------------------------------------------------------
# trace buffer + Chrome export (synthetic spans)
# ---------------------------------------------------------------------------


def _synthetic_buffer():
    t = TraceBuffer(name="pod-test")
    t.record(0, "submit", 0, arrival=0)
    t.record(1, "submit", 0, arrival=2)
    t.record(0, "admit", 1, replica="r0", slot=0)
    t.record(0, "prefill", 1, replica="r0", slot=0, positions=8, bucket=16,
             pages=0, prefix_hit=False)
    t.record(0, "decode_chunk", 2, replica="r0", slot=0, chunk=4)
    t.record(0, "complete", 2, replica="r0", slot=0, tokens=5,
             reason="length")
    t.record(1, "reject", 3, reason="oversized")
    return t


def test_trace_buffer_ring_and_validation():
    t = TraceBuffer(capacity=3)
    with pytest.raises(ValueError):
        # deliberately bad kind: proves TraceBuffer rejects it at runtime
        t.record(0, "not-a-kind", 0)  # repro: lint-ok[span-lifecycle]
    for i in range(5):
        t.record(i, "submit", i)
    assert t.recorded == 5 and len(t.events()) == 3 and t.dropped == 2
    assert [e.rid for e in t.events()] == [2, 3, 4]
    t.clear()
    assert t.recorded == 0 and t.status()["buffered"] == 0


def test_validate_span_log_accepts_legal_lifecycles():
    stats = validate_span_log([_synthetic_buffer()])
    assert stats == {"buffers": 1, "requests": 2, "events": 7}
    assert validate_span_log([]) == {"buffers": 0, "requests": 0,
                                     "events": 0}


def test_validate_span_log_rejects_illegal_transitions():
    # complete straight after submit: prefill/decode_chunk never happened
    t = TraceBuffer(name="pod-x")
    t.record(0, "submit", 0)
    t.record(0, "complete", 1, tokens=1, reason="length")
    with pytest.raises(ValueError, match="illegal transition"):
        validate_span_log([t])
    # nothing may follow a terminal span
    t = _synthetic_buffer()
    t.record(0, "decode_chunk", 9, replica="r0", slot=0, chunk=1)
    with pytest.raises(ValueError, match="after terminal"):
        validate_span_log([t])
    # a log may not START mid-lifecycle...
    t = TraceBuffer(name="pod-x")
    t.record(0, "decode_chunk", 0, replica="r0", slot=0, chunk=1)
    with pytest.raises(ValueError, match="starts with"):
        validate_span_log([t])
    # ...unless the ring dropped events (the true start fell off)
    t = TraceBuffer(name="pod-x", capacity=2)
    t.record(0, "submit", 0, arrival=0)
    t.record(0, "admit", 1, replica="r0", slot=0)
    t.record(0, "prefill", 1, replica="r0", slot=0, positions=4, bucket=8,
             pages=0, prefix_hit=False)
    assert t.dropped == 1
    assert validate_span_log([t])["events"] == 2
    # ticks must be monotone within a request
    t = TraceBuffer(name="pod-x")
    t.record(0, "submit", 5, arrival=5)
    t.record(0, "admit", 3, replica="r0", slot=0)
    with pytest.raises(ValueError, match="backwards"):
        validate_span_log([t])


def test_export_chrome_valid_and_validator_catches_corruption(tmp_path):
    path = tmp_path / "trace.json"
    trace = export_chrome([_synthetic_buffer()], path)
    stats = validate_chrome_trace(path)
    assert stats["events"] == len(trace["traceEvents"]) >= 5
    assert stats["requests"] == 2
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"queue", "prefill", "decode", "generate", "complete",
            "reject"} <= names
    # every non-metadata event carries the required keys + args.rid
    for e in trace["traceEvents"]:
        assert {"name", "ph", "ts", "pid"} <= set(e)
        if e["ph"] != "M":
            assert "rid" in e["args"]
    # corrupting per-request monotonicity must be caught
    bad = json.loads(path.read_text())
    xs = [e for e in bad["traceEvents"] if e["ph"] != "M"]
    xs[-1]["ts"] = -1
    with pytest.raises(ValueError, match="backwards"):
        validate_chrome_trace(bad)
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"traceEvents": []})
    missing = {"traceEvents": [{"name": "x", "ph": "i", "ts": 0}]}
    with pytest.raises(ValueError, match="pid"):
        validate_chrome_trace(missing)


def test_decomposition_and_recompute_from_synthetic_spans():
    buf = _synthetic_buffer()
    d = decomposition([buf])
    assert d["latency_count"] == 1          # rid 1 was rejected
    assert d["ttft_p50_ticks"] == 1 and d["ttft_p99_ticks"] == 1
    assert d["itl_p50_ticks"] == ((2 - 1) * 1000 // 4) / 1000.0
    reg = recompute_registry([buf])
    assert reg.total("requests_completed") == 1
    assert reg.total("requests_rejected") == 1
    assert reg.total("tokens_out") == 5
    empty = decomposition([TraceBuffer()])
    assert empty["latency_count"] == 0 and empty["ttft_p50_ticks"] == 0


def test_validator_requires_dur_on_complete_events():
    # a ph:"X" event with no dur at all is malformed, not 0-length: the
    # validator used to let it slide (only negative durs were caught)
    trace = export_chrome([_synthetic_buffer()])
    bad = json.loads(json.dumps(trace))
    x = next(e for e in bad["traceEvents"] if e["ph"] == "X")
    del x["dur"]
    with pytest.raises(ValueError, match="no 'dur'"):
        validate_chrome_trace(bad)
    # the unmodified export still validates
    validate_chrome_trace(trace)


def test_single_token_completions_excluded_from_itl_percentiles():
    t = TraceBuffer(name="pod-itl")
    # rid 0: 5 tokens over 4 decode ticks -> a real inter-token sample
    t.record(0, "submit", 0, arrival=0)
    t.record(0, "admit", 1, replica="r0", slot=0)
    t.record(0, "complete", 5, replica="r0", slot=0, tokens=5,
             reason="length")
    # rid 1: single-token completion -- no inter-token gap exists
    t.record(1, "submit", 0, arrival=0)
    t.record(1, "admit", 1, replica="r0", slot=1)
    t.record(1, "complete", 1, replica="r0", slot=1, tokens=1,
             reason="length")
    d = decomposition([t])
    assert d["latency_count"] == 2          # both still count for TTFT
    assert d["itl_count"] == 1              # but only rid 0 has an ITL
    # counting rid 1's itl_milliticks == 0 used to drag p50 to 0.5
    assert d["itl_p50_ticks"] == d["itl_p99_ticks"] == 1.0
    # the registry HISTOGRAM keeps recording the 0 sample: the
    # live-vs-recompute bitwise match is untouched by the report fix
    reg = recompute_registry([t])
    h = reg.merged_histogram("itl_milliticks")
    assert h.count == 2 and h.percentile(50) == 0


# ---------------------------------------------------------------------------
# end-to-end: spans + registry from a real served trace
# ---------------------------------------------------------------------------

IMAGEFILE = """
FROM scratch
ARCH llama3.2-3b-smoke
SHAPE decode_32k seq_len=64 global_batch=4
MESH local
PRECISION compute=float32 params=float32
COLLECTIVES generic
"""


@pytest.fixture(scope="module")
def rt(tmp_path_factory):
    from repro.core.runtime import Runtime
    rt = Runtime(tmp_path_factory.mktemp("stevedore"))
    rt.build(IMAGEFILE, tag="stable")
    return rt


def _requests(rng, n, *, base_rid=0, arrive_per_tick=4, max_gen=10):
    from repro.orchestrator import GenRequest
    return [
        GenRequest(rid=base_rid + i,
                   prompt=rng.integers(0, 256, int(rng.integers(3, 18))),
                   max_new_tokens=int(rng.integers(2, max_gen)),
                   arrival=i // arrive_per_tick)
        for i in range(n)
    ]


SPAN_ORDER = {"submit": 0, "route": 1, "admit": 2, "prefill": 3,
              "decode_chunk": 4, "complete": 5, "reject": 5}


@pytest.mark.orchestrator
def test_span_lifecycle_invariants_and_recompute_match(rt):
    """Every completed request's spans are monotone in tick and
    well-nested (submit <= admit <= decode chunks <= complete), and the
    aggregate metrics recomputed from the span log alone bitwise-match the
    live registry snapshot (same trace -> same numbers)."""
    from repro.orchestrator import ContinuousScheduler, GenRequest, Pod
    pod = Pod(rt, "stable", replicas=2, n_slots=3, max_len=56)
    sched = ContinuousScheduler(pod, fairness_cap=3)
    reqs = _requests(np.random.default_rng(3), 18)
    # one fleet-infeasible request: its reject span must recompute too
    giant = GenRequest(rid=900, prompt=np.arange(40, dtype=np.int64),
                       max_new_tokens=40)
    sched.submit(reqs + [giant])
    sched.run(max_ticks=5000)
    assert all(r.state == "done" for r in reqs)
    assert giant.state == "rejected"

    per_req = pod.trace.by_request()
    assert set(per_req) == {r.rid for r in reqs} | {giant.rid}
    for r in reqs:
        evs = per_req[r.rid]
        names = [e.name for e in evs]
        # exactly one of each lifecycle edge, in order
        assert names.count("submit") == 1
        assert names.count("admit") == 1
        assert names.count("prefill") == 1
        assert names.count("complete") == 1
        assert names[0] == "submit" and names[-1] == "complete"
        # monotone in tick, well-nested in lifecycle order
        ticks = [e.tick for e in evs]
        assert ticks == sorted(ticks)
        stages = [SPAN_ORDER[n] for n in names]
        assert stages == sorted(stages)
        sub, adm, comp = evs[0], evs[names.index("admit")], evs[-1]
        assert sub.tick == r.submit_tick and adm.tick == r.admit_tick
        assert comp.tick == r.done_tick
        assert comp.attr("tokens") == len(r.tokens) == r.max_new_tokens
        # decode chunks all inside [admit, complete]
        for e in evs:
            if e.name == "decode_chunk":
                assert adm.tick <= e.tick <= comp.tick
        # span attributes carry placement
        assert adm.attr("replica") == r.replica
        assert adm.attr("slot") is not None
    assert [e.name for e in per_req[giant.rid]] == ["submit", "reject"]

    # the served trace replays clean against the span state machine
    stats = validate_span_log([pod.trace])
    assert stats["requests"] == len(reqs) + 1

    # the determinism check: recompute the registry from spans alone.
    # snapshots now carry per-bucket exemplar rids, so this equality also
    # proves the live path (req.rid at completion) and the replay path
    # (lifecycle rid) pick identical exemplars.
    live = completion_snapshot(pod.metrics.snapshot())
    rec = completion_snapshot(recompute_registry([pod.trace]).snapshot())
    assert live == rec
    assert live["counters"]["requests_completed"] == len(reqs)
    assert live["counters"]["requests_rejected"] == 1
    # the p99 exemplar names a real completed request
    p99_rid = snapshot_exemplar(pod.metrics.snapshot(), "latency_ticks", 99)
    assert p99_rid in {r.rid for r in reqs}


@pytest.mark.orchestrator
def test_tokens_wasted_counts_chunk_overshoot(rt):
    """A budget-2 request under decode_chunk=4 takes its first token at
    prefill and finishes on the chunk's first decode tick: the other 3
    tokens of the dispatch are discarded and must be counted."""
    from repro.orchestrator import ContinuousScheduler, GenRequest, Pod
    pod = Pod(rt, "stable", replicas=1, n_slots=3, max_len=56,
              decode_chunk=4)
    eng = pod.engines[0]
    sched = ContinuousScheduler(pod, fairness_cap=3)
    req = GenRequest(rid=0, prompt=np.arange(4), max_new_tokens=2)
    sched.submit(req)
    sched.run(max_ticks=100)
    assert req.state == "done" and len(req.tokens) == 2
    assert eng.tokens_wasted == 3
    assert eng.status()["tokens_wasted"] == 3
    # a budget that lands exactly on the chunk boundary wastes nothing
    req2 = GenRequest(rid=1, prompt=np.arange(4), max_new_tokens=5)
    sched.submit(req2)
    sched.run(max_ticks=100)
    assert len(req2.tokens) == 5
    assert eng.tokens_wasted == 3
    out = sched.metrics.snapshot()
    assert snapshot_total(out, "tokens_wasted") == 3


@pytest.mark.orchestrator
def test_pod_trace_exports_valid_chrome_json(rt, tmp_path):
    from repro.orchestrator import ContinuousScheduler, Pod
    pod = Pod(rt, "stable", replicas=1, n_slots=3, max_len=56)
    sched = ContinuousScheduler(pod, fairness_cap=3)
    reqs = _requests(np.random.default_rng(5), 8)
    sched.submit(reqs)
    sched.run(max_ticks=2000)
    path = tmp_path / "serve_trace.json"
    export_chrome([pod.trace], path)
    stats = validate_chrome_trace(path)
    assert stats["requests"] == len(reqs)
    # the validator CLI gates CI on the same check
    from repro.orchestrator.obs.validate import main as validate_main
    with redirect_stdout(io.StringIO()) as buf:
        assert validate_main([str(path)]) == 0
    assert "OK" in buf.getvalue()
    assert validate_main([str(tmp_path / "missing.json")]) == 1


@pytest.mark.orchestrator
def test_router_policy_counters_and_ps_rendering(rt):
    """Spillover/rejection surface per placement policy in router status
    and `repro ps`; pod lines carry wasted= and '-' latency when idle."""
    from repro.cli import main as cli_main
    from repro.orchestrator import GenRequest, Pod, PodRouter
    small = Pod(rt, "stable", replicas=1, n_slots=2, max_len=24)
    big = Pod(rt, "stable", replicas=1, n_slots=2, max_len=56)
    router = PodRouter([small, big], policy="shortest-queue")
    # long request: never fits `small` (preferred while equally loaded),
    # spills to `big`
    long_req = GenRequest(rid=0, prompt=np.arange(20), max_new_tokens=10)
    # giant request: fits nowhere -> router-level rejection
    giant = GenRequest(rid=1, prompt=np.arange(60), max_new_tokens=30)
    router.submit([long_req, giant])
    router.run(max_ticks=2000)
    assert long_req.state == "done" and long_req.pod == big.pod_id
    assert giant.state == "rejected"
    assert router.spilled == 1 and len(router.rejected) == 1
    st_ = router.status()
    assert st_["by_policy"] == {"shortest-queue": {
        "routed": 1, "spillover": 1, "rejected": 1, "shed": 0}}
    # fleet rollup: pod completion metrics aggregate under the router
    assert snapshot_total(st_["metrics"], "requests_completed") == 1
    assert snapshot_total(st_["metrics"], "requests_rejected") == 1
    # the fleet-wide recompute sees the router-level reject span too
    rec = recompute_registry(router.trace_buffers())
    assert rec.total("requests_completed") == 1
    assert rec.total("requests_rejected") == 1

    with redirect_stdout(io.StringIO()) as buf:
        assert cli_main(["--root", str(rt.root), "ps"]) == 0
    out = buf.getvalue()
    assert "shortest-queue[spill=1,rej=1,shed=0]" in out
    assert "wasted=" in out
    # `small` served nothing: its latency renders '-', not a fake 0
    small_line = next(ln for ln in out.splitlines()
                      if ln.startswith(small.pod_id))
    assert "p50/p99=-/-" in small_line
    big_line = next(ln for ln in out.splitlines()
                    if ln.startswith(big.pod_id))
    assert "p50/p99=-/-" not in big_line


@pytest.mark.orchestrator
def test_top_renders_live_metrics(rt):
    """`repro top` reads queue/pool/latency off the state-file snapshots
    (requires a previously-served fleet in this runtime root)."""
    from repro.cli import main as cli_main
    from repro.orchestrator import ContinuousScheduler, Pod
    pod = Pod(rt, "stable", replicas=1, n_slots=3, max_len=56, paged=True,
              page_size=8)
    sched = ContinuousScheduler(pod, fairness_cap=3)
    reqs = _requests(np.random.default_rng(9), 6)
    sched.submit(reqs)
    sched.run(max_ticks=2000)
    with redirect_stdout(io.StringIO()) as buf:
        assert cli_main(["--root", str(rt.root), "top"]) == 0
    out = buf.getvalue()
    assert "QUEUE" in out and "TTFT" in out and "P99-RID" in out
    line = next(ln for ln in out.splitlines() if ln.startswith(pod.pod_id))
    # the exemplar column names one of the rids this fleet actually served
    assert any(tok.isdigit() and int(tok) < 6 for tok in line.split())
    assert "/" in line          # pool occupancy + latency percentiles
    assert " -" not in line.split(pod.pod_id)[1][:20] or True


@pytest.mark.orchestrator
def test_serve_trace_flag_writes_valid_trace(rt, tmp_path):
    from repro.launch.serve import serve_continuous
    path = tmp_path / "out.json"
    args = SimpleNamespace(slots=3, prompt_len=8, gen=6, requests=5, seed=0,
                           platform=None, replicas=1, fairness_cap=4,
                           arrive_per_tick=8, paged=False, page_size=8,
                           pods=1, policy="shortest-queue",
                           trace=str(path))
    with redirect_stdout(io.StringIO()):
        out = serve_continuous(rt, "stable", args)
    assert path.exists()
    assert validate_chrome_trace(path)["requests"] == 5
    d = out["decomposition"]
    assert d["latency_count"] == 5
    assert d["ttft_p99_ticks"] >= 0 and d["itl_p50_ticks"] >= 0
    assert out["latency_count"] == 5
    assert "tokens_wasted" in out


# ---------------------------------------------------------------------------
# program spans on the profiler's clock (obs.tracing.span)
# ---------------------------------------------------------------------------

# every span one step writes, with the span it sits in
SPAN_PARENTS = {
    "repro.step": None, "repro.admit": "repro.step",
    "repro.prefill": "repro.step", "repro.compile": "repro.prefill",
    "repro.prefill.dispatch": "repro.prefill",
    "repro.prefill.wait": "repro.prefill",
    "repro.prefill.insert": "repro.prefill",
    "repro.decode": "repro.step", "repro.decode.alloc": "repro.decode",
    "repro.decode.dispatch": "repro.decode",
    "repro.decode.wait": "repro.decode",
    "repro.decode.readback": "repro.decode",
    "repro.decode.walk": "repro.decode",
    "repro.observe": "repro.step", "repro.write_state": "repro.step"}


def _program_spans(log_dir):
    """[name, parent name, attributes] of every ``repro.*`` host event in
    the profiler's trace under ``log_dir``, in start order."""
    import glob

    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    evs = sorted(([e.name, e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats)]
                  for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for e in line.events
                  if e.name.startswith("repro.")),
                 key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, s, end, attrs in evs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append([name, stack[-1][0] if stack else None, attrs])
        stack.append((name, end))
    return out


@pytest.mark.orchestrator
def test_step_writes_program_spans_nested_with_attributes(rt, tmp_path):
    """One scheduler step of a tiny paged model under the profiler: it
    admits a request, prefills it (compiling the bucket), decodes the
    chunk that completes it, observes it and writes the pod's state. Every
    span of that work is in the trace, inside the span of its caller, with
    the counts at its boundary."""
    import jax

    from repro.orchestrator import ContinuousScheduler, GenRequest, Pod
    pod = Pod(rt, "stable", replicas=1, n_slots=2, max_len=56, paged=True,
              page_size=8, decode_chunk=4)
    sched = ContinuousScheduler(pod)
    req = GenRequest(rid=5, prompt=np.arange(6), max_new_tokens=2)
    sched.submit(req)
    jax.profiler.start_trace(str(tmp_path))
    try:
        sched.step()
    finally:
        jax.profiler.stop_trace()
    assert req.state == "done"
    spans = _program_spans(tmp_path)
    assert sorted({n for n, _, _ in spans}) == sorted(SPAN_PARENTS)
    for name, parent, _ in spans:
        assert parent == SPAN_PARENTS[name], name
    attrs = {n: a for n, _, a in spans}
    assert attrs["repro.step"] == {"tick": 0}
    assert attrs["repro.admit"] == {"rid": 5, "queued_ticks": 0}
    assert attrs["repro.prefill"] == {"rid": 5, "positions": 6,
                                      "bucket": 16, "prefix_hit": 0}
    assert attrs["repro.compile"]["step"] == "prefill_slot_paged"
    assert attrs["repro.compile"]["hit"] in (0, 1)
    assert attrs["repro.decode"] == {"active": 1, "chunk": 4}
    assert attrs["repro.decode.walk"] == {"tokens": 1}
    assert attrs["repro.observe"] == {"requests": 1}
    # one of each per step: the step's work is not cut per token
    assert len(spans) == len(SPAN_PARENTS)


@pytest.mark.orchestrator
def test_span_log_replays_byte_identical_under_the_profiler(rt, tmp_path):
    """The tick-clocked span log of one request trace is the same bytes
    whether the profiler records the program's spans or not."""
    import jax

    from repro.orchestrator import ContinuousScheduler, Pod
    from repro.orchestrator.obs import dump_span_log

    def serve_once(path, profile):
        pod = Pod(rt, "stable", replicas=1, n_slots=3, max_len=56,
                  paged=True, page_size=8, pod_id="pod-replay")
        sched = ContinuousScheduler(pod, fairness_cap=3)
        sched.submit(_requests(np.random.default_rng(11), 8))
        if profile:
            jax.profiler.start_trace(str(tmp_path / "profile"))
        try:
            sched.run(max_ticks=2000)
        finally:
            if profile:
                jax.profiler.stop_trace()
        return dump_span_log(pod.trace, path).read_bytes()

    plain = serve_once(tmp_path / "plain.json", False)
    traced = serve_once(tmp_path / "traced.json", True)
    assert traced == plain
    assert json.loads(plain)["events"]
    assert _program_spans(tmp_path / "profile")
