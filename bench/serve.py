"""Set up a cell's Pod and drive it for one measured window.

The Pod is built as a user of ``repro serve`` builds it: an Imagefile
through ``Runtime``, one ``Pod`` of one replica with the paged pool and the
prefix registry, one ``ContinuousScheduler``. The harness then submits the
cell's requests at their due times and calls ``ContinuousScheduler.step``
in a loop; after each return it stamps every new token of every request
with the host clock. Every time the benchmark reports comes from those
stamps and from spans around calls it makes itself.
"""

from __future__ import annotations

import contextlib
import gc
import inspect
import itertools
import time
from dataclasses import dataclass, field

import numpy as np

from bench import traffic as traffic_mod
from bench.spec import BENCH, loader, reference

# what the Pod writes (runtime registry, overlays, pod state) stays here
RUNTIME_ROOT = BENCH / ".work" / "runtime"


@dataclass
class Record:
    """One request as its client sees it."""
    req: object                       # the program's GenRequest
    prompt_len: int
    due: float | None                 # host time it was due; None: before
    submitted: float = 0.0            # the window (fill and warm-up)
    times: list = field(default_factory=list)   # host time of each token
    admit_t: float | None = None      # start of the step that admitted it
    done_at: float | None = None
    failed: bool = False
    client: int | None = None


@dataclass
class StepRecord:
    """One ``ContinuousScheduler.step`` call in the window."""
    t0: float
    t1: float
    decoded: list                     # (context at the chunk's first tick,
    #                                   ticks that delivered a token) per
    #                                   slot active at the decode dispatch


@dataclass
class Window:
    t0: float
    t1: float
    records: list
    steps: list
    counters0: dict
    counters1: dict
    compiles: int
    late: list                        # open loop: submit time - due time


class Server:
    """A cell's Pod, loaded with the benchmark's weights and warmed up."""

    def __init__(self, cell: dict, seed: int):
        import jax
        from repro.core.runtime import Runtime
        from repro.orchestrator import ContinuousScheduler, Pod

        self.cell = cell
        self.seed = seed
        cfg = cell["config"]
        serving = cfg["serving"]
        self.ref = reference(cfg["reference"])
        self.dims = self.ref.dims(cfg)

        t = time.perf_counter()
        self.phases = {}
        rt = Runtime(RUNTIME_ROOT)
        image = rt.build("\n".join(["FROM scratch", *cfg["imagefile"]]) + "\n",
                         tag=cfg["name"])
        probe = rt.run(image)
        self._check_arch(probe.arch)
        defaults = inspect.signature(Pod.__init__).parameters
        self.page_size = defaults["page_size"].default
        page = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in jax.tree.leaves(
                       probe.paged_cache_specs(1, self.page_size)))
        self.n_pages = int(serving["kv_pool_bytes"]) // page
        self.pool_bytes = self.n_pages * page
        self.slots = int(serving["slots"])
        self.phases["image"] = time.perf_counter() - t
        t = time.perf_counter()
        self.pod = Pod(rt, image, replicas=1,
                       n_slots=self.slots, max_len=int(serving["max_len"]),
                       seed=seed, paged=True, page_size=self.page_size,
                       n_pages=self.n_pages, prefix_cache=True,
                       spill_pages=0)
        self.engines = self.pod.engines
        self.chunk = self.engines[0].chunk
        self.phases["pod"] = time.perf_counter() - t
        t = time.perf_counter()
        self._load_weights(cfg, seed)
        self.phases["weights"] = time.perf_counter() - t
        self.sched = ContinuousScheduler(self.pod)
        self.vocab = self.dims["vocab"]
        self._rid = 0

    def _check_arch(self, arch) -> None:
        d = self.dims
        have = dict(d=arch.d_model, h=arch.n_heads, kv=arch.n_kv_heads,
                    hd=arch.head_dim_, f=arch.d_ff, layers=arch.n_layers,
                    vocab=arch.vocab_size,
                    layernorm=arch.norm == "layernorm",
                    theta=float(arch.rope_theta))
        want = {k: d[k] for k in have}
        if have != want:
            raise SystemExit(f"the image's architecture {have} is not the "
                             f"configuration {want}")

    def _load_weights(self, cfg: dict, seed: int) -> None:
        """Replace the Pod's parameters (its own random init, or an
        earlier seed's) with the benchmark's seeded weights, which the
        reference can regenerate."""
        import jax
        c = self.engines[0].container
        self.pod.drop_params(self.pod.image.digest)
        for e in self.engines:
            e.params = None
        gc.collect()
        params = loader(cfg["reference"]).program_params(
            self.ref, cfg, seed, c.abstract_params(), c.param_shardings())
        params = jax.block_until_ready(params)
        for e in self.engines:
            e.params = params
        self.params_bytes = sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(params))
        self.seed = seed

    def reload(self, seed: int) -> None:
        """Serve another seed's weights on the same Pod and executables,
        dropping every request still queued or in flight."""
        for e in self.engines:
            for req in list(e.active.values()):
                e.preempt(req, self.sched.tick)
        while self.sched.queue.has_ready(self.sched.tick):
            self.sched.queue.pop_ready(self.sched.tick)
        self._load_weights(self.cell["config"], seed)

    # -- requests ----------------------------------------------------------
    def request(self, prompt, max_new: int, prefix_len: int):
        from repro.orchestrator import GenRequest
        self._rid += 1
        return GenRequest(rid=self._rid, prompt=np.asarray(prompt, np.int32),
                          max_new_tokens=int(max_new),
                          arrival=self.sched.tick, prefix_len=prefix_len)

    def warm_up(self, mix: dict) -> int:
        """Serve one request per prefill shape the mix can reach (and, with
        a shared prefix, one per suffix shape over the cached prefix), so
        every executable the window uses is compiled and has run once.
        Returns the number of warm-up requests."""
        t = time.perf_counter()
        rng = np.random.default_rng((self.seed, 0x5EED))
        eng = self.engines[0]
        shared = int(mix.get("shared_prefix", 0))
        lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
        lengths = sorted({min(max(b, lo), hi) for b in
                          (eng.bucket(p) for p in range(lo, hi + 1))})
        prefix = traffic_mod.shared_prefix(mix, self.vocab, self.seed)
        reqs = []
        if shared:
            # the miss path first, with the mix's own prefix: it registers
            # the prefix, and every later request of the run hits it.
            # Other miss shapes get prefixes of their own.
            misses = sorted({min(max(eng.bucket(shared + n), shared + lo),
                                 shared + hi) - shared for n in lengths})
            for i, n in enumerate(misses):
                head = prefix if i == 0 else rng.integers(
                    0, self.vocab, shared, dtype=np.int32)
                self.sched.submit(self.request(np.concatenate(
                    [head, rng.integers(0, self.vocab, n, dtype=np.int32)]),
                    self.chunk + 1, shared))
                self.sched.run()
        for n in lengths:
            body = rng.integers(0, self.vocab, n, dtype=np.int32)
            reqs.append(self.request(np.concatenate([prefix, body]),
                                     self.chunk + 1, shared))
        self.sched.submit(reqs)
        self.sched.run()
        self.phases["warm_up"] = time.perf_counter() - t
        return len(reqs)

    # -- counters ----------------------------------------------------------
    def counters(self) -> dict:
        m = self.pod.metrics
        out = {name: m.total(name) for name in (
            "prefill_positions", "prefix_tokens_saved", "decode_dispatches",
            "decode_ticks", "tokens_wasted", "preemptions", "prefix_hits",
            "prefix_misses", "tokens_generated")}
        out["prefill_s"] = sum(e.prefill_s for e in self.engines)
        out["decode_s"] = sum(e.decode_s for e in self.engines)
        out["serve_compiles"] = sum(
            s["hits"] + s["misses"] for e in self.engines
            for s in e.container.serve_compile_stats.values())
        return out

    def prefix_saved(self) -> dict:
        """Prompt tokens each request took from the prefix registry, by
        request id, from the program's prefill spans."""
        return {rid: max((e.attr("tokens_saved", 0) or 0) for e in evs
                         if e.name == "prefill")
                for rid, evs in self.pod.trace.by_request().items()
                if any(e.name == "prefill" for e in evs)}

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        for e in self.engines:
            e.release()
        self.pod.drop_params(self.pod.image.digest)
        self.sched = None
        gc.collect()


class CompileCounter:
    """Counts JAX traces and backend compiles while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event in self.EVENTS:
            self.count += 1


def _stamp(in_flight, before, s0, s1, closed, waiting, steps):
    """After one step: stamp each request's new tokens with the step's
    end, note admissions, completions and failures, and record which
    slots the step's decode dispatch carried. Returns what is still in
    flight."""
    decoded = []
    still = []
    for rec in in_flight:
        req = rec.req
        n0, n1 = before.get(id(rec), 0), len(req.tokens)
        if n0 == 0 and n1 > 0:
            rec.admit_t = s0
        if n1 > 0 and req.admit_tick >= 0 and (
                req.state == "running" or (req.state == "done"
                                           and n1 > max(n0, 1))):
            # active at this step's decode dispatch: its first tick
            # attends prompt + tokens before the chunk positions, and its
            # ticks up to its last delivered token did useful work
            decoded.append((rec.prompt_len + max(n0, 1), n1 - max(n0, 1)))
        rec.times.extend([s1] * (n1 - n0))
        if req.state in ("done", "rejected", "shed"):
            rec.failed = req.state != "done"
            rec.done_at = s1
            if closed:
                waiting.append((s1, rec.client))
        else:
            still.append(rec)
    steps.append(StepRecord(s0, s1, decoded))
    return still


def run_window(server: Server, mix: dict, seconds: float, seed: int,
               compiles: CompileCounter, tracer=None,
               annotate=None) -> Window:
    """Drive the Pod for ``seconds`` of host time at the mix's load.

    A closed loop fills every client's slot first; an open loop serves its
    warm-in arrivals (due before 0) first. Either way the window opens on a
    step boundary with the load in flight. ``tracer`` (optional) is called
    as ``tracer(now, t_end)`` between steps and may start or stop the
    profiler; ``annotate(name)`` (optional) gives a context that marks the
    host's spans in the profiler's trace."""
    ann = annotate or (lambda name: contextlib.nullcontext())
    sched = server.sched
    records: list[Record] = []
    in_flight: list[Record] = []
    late: list = []
    waiting: list = []                       # closed loop: (due, client)
    clock = time.perf_counter

    def submit(r, due, client=None):
        rec = Record(server.request(r.prompt, r.max_new_tokens, r.prefix_len),
                     len(r.prompt), due, client=client)
        rec.submitted = clock()
        sched.submit(rec.req)
        records.append(rec)
        in_flight.append(rec)
        return rec

    closed = mix["loop"] == "closed"
    schedule: list = []
    if closed:
        seq = itertools.cycle(traffic_mod.closed_loop(mix, server.vocab,
                                                      seed))
        waiting = [(None, c) for c in range(int(mix["clients"]))]
        while waiting or sched.queue.pending:     # fill the slots
            for _, client in waiting:
                submit(next(seq), None, client=client)
            waiting = []
            before = {id(r): len(r.req.tokens) for r in in_flight}
            s0 = clock()
            sched.step()
            in_flight = _stamp(in_flight, before, s0, clock(), True,
                               waiting, [])
        for rec in records:                 # delivered before the window
            rec.times = [None] * len(rec.times)
    else:
        schedule = traffic_mod.open_loop(mix, seconds, server.vocab, seed)
    nxt = 0
    base = 0.0                              # host time at which due_s is 0

    def serve_until(t_stop, horizon, steps, trace=None):
        """Submit what falls due (arrivals with ``due_s`` under
        ``horizon``) and step, until the first step boundary at or after
        ``t_stop``."""
        nonlocal nxt, in_flight, waiting

        def due_next():
            if nxt < len(schedule) and schedule[nxt].due_s < horizon:
                return base + schedule[nxt].due_s
            return None

        while True:
            now = clock()
            if now >= t_stop:
                return
            if trace is not None:
                trace(now, t_stop)
            with ann("submit"):
                while (d := due_next()) is not None and d <= now:
                    submit(schedule[nxt], d)
                    late.append(clock() - d)
                    nxt += 1
                for due, client in waiting:
                    submit(next(seq), due, client=client)
                waiting = []
            if not sched.busy:
                wake = due_next()
                with ann("wait"):
                    time.sleep(max(0.0, min(wake or t_stop, t_stop)
                                   - clock()))
                continue
            before = {id(r): len(r.req.tokens) for r in in_flight}
            s0 = clock()
            with ann("step"):
                sched.step()
            s1 = clock()
            with ann("client"):
                in_flight = _stamp(in_flight, before, s0, s1, closed,
                                   waiting, steps)

    warm = 0.0 if closed else float(mix.get("warm_in_s", 0))
    if warm > 0:
        base = clock() + warm
        serve_until(base, 0.0, [])
    c0 = server.counters()
    compiles.count = 0
    compiles.active = True
    t0 = clock()
    base = t0               # in-window arrivals keep their gaps from here
    t_end = t0 + seconds
    steps: list[StepRecord] = []
    serve_until(t_end, float("inf"), steps, tracer)
    t1 = clock()
    compiles.active = False
    if tracer is not None:
        tracer(t1, t1)
    return Window(t0, t1, records, steps, c0, server.counters(),
                  compiles.count, late)
