"""Cross-host fabric invariants: the codec round-trips requests bit-for-
bit, a transport-connected fleet is token-identical to a single
scheduler, eviction removes exactly the victim's ring entries, a dead
pod's in-flight work re-routes EXACTLY once (and resumes bitwise), a
flapping link (dropped replies, live worker) never evicts or duplicates
work, and the outstanding-token ledger settles to zero -- including the
PodRouter deadline-shed regression that motivated this sweep."""

import numpy as np
import pytest

from repro.core.runtime import Runtime
from repro.orchestrator import (
    ContinuousScheduler,
    FabricRouter,
    GenRequest,
    Pod,
    decode_request,
    encode_request,
    loopback_spawner,
)
from repro.orchestrator.fabric import decode_frame, encode_frame
from repro.orchestrator.obs import validate_fleet_closure, validate_span_log

pytestmark = pytest.mark.orchestrator

IMAGEFILE = """
FROM scratch
ARCH {arch}
SHAPE decode_32k seq_len=64 global_batch=4
MESH local
PRECISION compute=float32 params=float32
COLLECTIVES generic
"""

POD_KWARGS = dict(replicas=1, n_slots=2, max_len=96)
MAX_TICKS = 5000


@pytest.fixture(scope="module")
def rt(tmp_path_factory):
    rt = Runtime(tmp_path_factory.mktemp("stevedore"))
    rt.build(IMAGEFILE.format(arch="llama3.2-3b-smoke"), tag="stable")
    return rt


def _requests(n, *, seed=0, arrive_per_tick=6):
    rng = np.random.default_rng(seed)
    return [GenRequest(
        rid=i,
        prompt=rng.integers(0, 256, int(rng.integers(4, 16))),
        max_new_tokens=int(rng.integers(4, 14)),
        arrival=i // arrive_per_tick) for i in range(n)]


def _fabric(rt, **kw):
    spawn = loopback_spawner(rt, rt.pull("stable"), pod_kwargs=POD_KWARGS)
    kw.setdefault("fleet", f"t{abs(hash(str(sorted(kw.items())))) % 10**8}")
    return FabricRouter(spawn, runtime=rt, **kw)


def _drain(router):
    while router.busy and router.tick < MAX_TICKS:
        router.step()
    assert not router.busy, "fabric run did not converge"


def _oracle(rt, reqs):
    """Single-scheduler token oracle: greedy decode + seeded params make
    tokens a function of (prompt, budget) only, so ONE pod running the
    whole trace is the parity reference for every fleet topology."""
    pod = Pod(rt, "stable", **POD_KWARGS)
    sched = ContinuousScheduler(pod)
    sched.submit(reqs)
    sched.run(max_ticks=MAX_TICKS)
    assert all(r.state == "done" for r in reqs)
    return {r.rid: list(r.tokens) for r in reqs}


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------

def test_frame_codec_skips_stray_output():
    msg = {"t": "hb", "tick": 7, "pod": "fab-0"}
    raw = encode_frame(msg)
    assert raw.startswith(b"\x1e") and raw.endswith(b"\n")
    assert decode_frame(raw) == msg
    # a worker's stdout carries library prints too: only frames parse
    assert decode_frame(b"some library print\n") is None
    assert decode_frame(b"\x1enot json\n") is None
    assert decode_frame(b"\x1e[1,2]\n") is None   # frames are objects
    assert decode_frame(raw.decode()) == msg      # str form too


def test_request_codec_roundtrips_resume_state():
    rng = np.random.default_rng(3)
    req = GenRequest(rid=42, prompt=rng.integers(0, 256, 9),
                     max_new_tokens=12, eos_id=7, arrival=3,
                     frontend=rng.standard_normal((5, 16)).astype(
                         np.float32),
                     prefix_len=4, priority="batch", deadline_ticks=50)
    # mid-flight resume state: what a re-route must carry to a survivor
    req.state = "preempted"
    req.tokens = [11, 22, 33]
    req.submit_tick, req.admit_tick = 2, 5
    req.preemptions, req.reroutes = 1, 1
    back = decode_request(decode_frame(encode_frame(
        {"t": "submit", "req": encode_request(req)}))["req"])
    assert back.rid == req.rid
    np.testing.assert_array_equal(back.prompt, np.asarray(req.prompt))
    assert back.prompt.dtype == np.int32
    np.testing.assert_array_equal(back.frontend, req.frontend)
    assert back.frontend.dtype == np.float32
    for f in ("max_new_tokens", "eos_id", "arrival", "prefix_len",
              "priority", "deadline_ticks", "state", "tokens",
              "submit_tick", "admit_tick", "preemptions", "reroutes"):
        assert getattr(back, f) == getattr(req, f), f
    # no frontend is preserved as None, not a zero-size array
    bare = GenRequest(rid=1, prompt=np.arange(3), max_new_tokens=2)
    assert decode_request(encode_request(bare)).frontend is None


# ---------------------------------------------------------------------------
# serving parity over the transport
# ---------------------------------------------------------------------------

def test_loopback_fleet_token_parity_with_single_scheduler(rt):
    """Framing every request/token through the codec must not change a
    single token: the 2-pod fabric replays the trace bitwise-identical
    to one scheduler owning it all, and the pooled span log closes."""
    oracle = _oracle(rt, _requests(14))
    router = _fabric(rt, pods=2, min_pods=2)
    reqs = _requests(14)
    router.submit(reqs)
    _drain(router)
    assert all(r.state == "done" for r in reqs)
    assert {r.rid: list(r.tokens) for r in reqs} == oracle
    assert router.outstanding_total == 0
    buffers = router.trace_buffers()
    validate_span_log(buffers)
    closure = validate_fleet_closure(buffers)
    assert closure["routed"] == closure["closed"] == 14
    router.close()


# ---------------------------------------------------------------------------
# eviction / reroute
# ---------------------------------------------------------------------------

def _kill_mid_decode(router):
    while router.busy and router.tick < MAX_TICKS:
        victim = next(
            (m for m in router.members.values()
             if any(r.tokens and len(r.tokens) < r.max_new_tokens
                    for r in m.assigned.values())),
            None)
        if victim is not None:
            victim.transport.kill()
            return victim
        router.step()
    raise AssertionError("no member was ever mid-decode")


def test_eviction_removes_exactly_victims_ring_entries(rt):
    """The hash ring after an eviction is the old ring minus precisely
    the victim's vnodes -- survivors' entries (hash AND position) are
    untouched, so only the victim's keyspace reassigns."""
    router = _fabric(rt, pods=3, min_pods=1, policy="consistent-hash",
                     vnodes=16)
    before = list(router._ring)
    victim = list(router.members.values())[1]
    victim.transport.kill()
    router.step()               # eviction sweep fires inside the tick
    assert victim.pod_id not in router.members
    expect = [(h, p) for h, p in before if p != victim.pod_id]
    assert router._ring == expect
    assert len(before) - len(router._ring) == 16
    assert router._ring_keys == [h for h, _ in router._ring]
    _drain(router)
    router.close()


def test_reroute_exactly_once_and_bitwise_resume(rt):
    """Kill a pod mid-decode: every one of its in-flight requests lands
    on a survivor EXACTLY once (reroutes == 1, single re-admission), the
    resumed continuations are token-identical to an unkilled run, and
    the ledger settles to zero."""
    oracle = _oracle(rt, _requests(14))
    router = _fabric(rt, pods=2, min_pods=2)
    reqs = _requests(14)
    router.submit(reqs)
    victim = _kill_mid_decode(router)
    inflight = sorted(victim.assigned)
    assert inflight, "victim had no in-flight work at kill time"
    _drain(router)
    assert all(r.state == "done" for r in reqs)
    assert {r.rid: list(r.tokens) for r in reqs} == oracle
    fab = router.status()["fabric"]
    assert fab["evictions"] == 1
    assert fab["reroutes"] == len(inflight)
    for r in reqs:
        assert r.reroutes == (1 if r.rid in inflight else 0), r.rid
    assert router.outstanding_total == 0
    buffers = router.trace_buffers()
    validate_span_log(buffers)   # would fail on a double-admit lifecycle
    closure = validate_fleet_closure(buffers)
    assert closure["rerouted"] == len(inflight)
    # exactly-once on the wire too: one route + one reroute span per
    # moved rid, never two reroutes
    spans = [e for b in buffers for e in b.events()]
    for rid in inflight:
        names = [e.name for e in spans if e.rid == rid]
        assert names.count("route") == 1
        assert names.count("reroute") == 1
    router.close()


def test_flapping_member_never_evicted_or_duplicated(rt):
    """Dropped replies below miss_limit (the worker is alive, the link
    flaps) must not evict: the member recovers on the next beat and no
    request is re-routed or re-admitted -- flapping is invisible in the
    output."""
    oracle = _oracle(rt, _requests(10))
    router = _fabric(rt, pods=2, min_pods=2, heartbeat_every=1,
                     miss_limit=4)
    reqs = _requests(10)
    router.submit(reqs)
    flappy = next(iter(router.members.values()))
    for _ in range(3):
        if not router.busy:
            break
        # drop this member's next 2 replies (heartbeat + step): the
        # worker still processes both messages, only the link is lossy
        flappy.transport.muted = 2
        router.step()
        assert flappy.pod_id in router.members, "flapping pod evicted"
        assert flappy.missed < router.miss_limit
        router.step()            # clean tick: beat lands, missed resets
        assert flappy.missed == 0
    _drain(router)
    assert all(r.state == "done" for r in reqs)
    assert {r.rid: list(r.tokens) for r in reqs} == oracle
    fab = router.status()["fabric"]
    assert fab["evictions"] == 0 and fab["reroutes"] == 0
    assert all(r.reroutes == 0 and r.preemptions == 0 for r in reqs)
    assert router.outstanding_total == 0
    validate_span_log(router.trace_buffers())
    router.close()


def test_draining_floor_and_infeasible_reject(rt):
    """Fleet-level placement edge cases: a request no member can EVER
    fit is rejected (terminal, reasoned) without wedging the fleet, and
    the elastic floor refuses to drop below min_pods."""
    router = _fabric(rt, pods=1, min_pods=1)
    huge = GenRequest(rid=0, prompt=np.arange(1, 80),
                      max_new_tokens=80)
    ok = GenRequest(rid=1, prompt=np.arange(1, 6), max_new_tokens=4)
    router.submit([huge, ok])
    _drain(router)
    assert huge.state == "rejected"
    assert huge.finish_reason == "oversized" and huge.error
    assert ok.state == "done" and len(ok.tokens) == 4
    assert router.outstanding_total == 0
    # reject is terminal at the ROUTER tier: closure still accounts it
    closure = validate_fleet_closure(router.trace_buffers())
    assert closure["routed"] == 1 and closure["closed"] == 1
    router.close()


# ---------------------------------------------------------------------------
# ledger conservation (the bugfix sweep)
# ---------------------------------------------------------------------------

def test_podrouter_ledger_settles_after_deadline_sheds(rt):
    """Regression: scheduler-tier deadline sheds never debited the
    PodRouter outstanding ledger, so a shed burst over-counted the pod
    forever and shortest-queue routed around it. After a drained run
    with sheds the ledger must be exactly zero."""
    from repro.orchestrator import PodRouter
    pod = Pod(rt, "stable", replicas=1, n_slots=1, max_len=64)
    router = PodRouter([pod])
    hog = GenRequest(rid=0, prompt=np.arange(1, 6), max_new_tokens=12)
    doomed = [GenRequest(rid=1 + i, prompt=np.arange(1, 6),
                         max_new_tokens=8, priority="batch",
                         deadline_ticks=2) for i in range(3)]
    router.submit([hog] + doomed)
    router.run(max_ticks=2000)
    assert hog.state == "done"
    assert all(r.state == "shed" and r.finish_reason == "deadline"
               for r in doomed)
    assert sum(router._outstanding.values()) == 0, \
        "deadline sheds leaked from the outstanding-token ledger"
    # the pod is still routable at its true (empty) load
    post = GenRequest(rid=50, prompt=np.arange(1, 6), max_new_tokens=4)
    router.submit(post)
    router.run(max_ticks=2000)
    assert post.state == "done"
    assert sum(router._outstanding.values()) == 0


def test_fabric_ledger_conserved_through_churn(rt):
    """The fabric ledger survives the full churn matrix -- completions,
    an eviction + reroutes, elastic spawn/retire -- and lands on zero."""
    router = _fabric(rt, pods=1, min_pods=1, max_pods=3,
                     scale_up_tokens=30, scale_idle_ticks=4)
    reqs = _requests(16)
    router.submit(reqs)
    _kill_mid_decode(router)
    _drain(router)
    assert all(r.state == "done" for r in reqs)
    assert router.outstanding_total == 0
    for _ in range(20):          # idle through drains + retires
        router.step()
    assert router.outstanding_total == 0
    assert len(router.members) >= router.min_pods
    router.close()


# -- one process per chip -----------------------------------------------------

@pytest.mark.parametrize(
    "initialized,backend,platforms,chips,live,refused", [
        (True, "tpu", None, 1, 0, "already holds the tpu backend"),
        (True, "cpu", None, 4, 3, None),       # CPU workers share the host
        (False, None, "cpu", 4, 3, None),
        (False, None, None, 4, 0, None),       # the first worker gets them
        (False, None, None, 4, 1, "each worker claims every chip"),
        (False, None, None, 0, 5, None),       # no chip to claim
    ], ids=["parent-holds-chip", "parent-on-cpu", "jax-platforms-cpu",
            "first-worker", "second-worker", "chipless-host"])
def test_proc_worker_refused_without_a_chip_of_its_own(
        monkeypatch, initialized, backend, platforms, chips, live, refused):
    from repro.orchestrator import fabric
    monkeypatch.setattr(fabric.xla_bridge, "backends_are_initialized",
                        lambda: initialized)
    monkeypatch.setattr(fabric.jax, "default_backend", lambda: backend)
    monkeypatch.setattr(fabric, "_host_chips", lambda: chips)
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    if refused is None:
        fabric._check_worker_gets_chip(live)
    else:
        with pytest.raises(RuntimeError, match=refused):
            fabric._check_worker_gets_chip(live)


def test_proc_spawner_refuses_before_starting_a_process(monkeypatch, tmp_path):
    """A refused worker is refused at once: no process is started, so
    nothing can hang waiting for a chip."""
    from repro.orchestrator import fabric
    monkeypatch.setattr(fabric.xla_bridge, "backends_are_initialized",
                        lambda: True)
    monkeypatch.setattr(fabric.jax, "default_backend", lambda: "tpu")
    started = []
    monkeypatch.setattr(fabric, "ProcTransport",
                        lambda *a, **k: started.append(a))
    spawn = fabric.proc_spawner(tmp_path, imagefile="FROM scratch\n")
    with pytest.raises(RuntimeError, match="--fabric loopback"):
        spawn("pod-0", 0)
    assert started == []
