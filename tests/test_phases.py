"""Phases (`Tracer.phase`): self time into `phase.<name>` timers, the same
segments as profiler trace events, waits timed but never annotated, and
the sites in the server, the spillover planner and the executor's host
loops. Clocks and annotations are injected: nothing here sleeps against a
threshold."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from janusgraph_tpu.observability import registry, tracer
from janusgraph_tpu.observability.metrics_core import TelemetryRegistry
from janusgraph_tpu.observability.spans import Tracer

SERVER_PHASES = ("server.read", "server.admit", "server.evaluate",
                 "server.serialize")
SPILL_PHASES = ("spill.recognize", "spill.lock_wait", "spill.plan",
                "spill.reduce", "spill.publish")
EXECUTOR_PHASES = ("executor.setup", "executor.dispatch", "executor.fetch",
                   "executor.publish")


class FakeClock:
    """Nanoseconds that pass only when a test says so."""

    def __init__(self):
        self.now = 1_000

    def __call__(self):
        return self.now

    def tick(self, ns):
        self.now += ns


class Annotations:
    """Stands in for jax.profiler.TraceAnnotation: records enter / exit."""

    def __init__(self):
        self.events = []

    def __call__(self, name):
        events = self.events

        class _Event:
            def __enter__(self):
                events.append(("enter", name))

            def __exit__(self, *exc):
                events.append(("exit", name))

        return _Event()


@pytest.fixture
def rig():
    clock, notes = FakeClock(), Annotations()
    tr = Tracer(clock=clock)
    tr.registry = TelemetryRegistry()
    tr.annotation = notes
    return tr, clock, notes


def _total_ns(tr, name):
    return tr.registry.timer("phase." + name).total_ns


def _moved(before, name, field="count"):
    after = registry.snapshot().get("phase." + name, {})
    return after.get(field, 0) - before.get("phase." + name, {}).get(field, 0)


# ------------------------------------------------------------- the primitive

def test_phases_tile_outer_self_time_excludes_inner(rig):
    tr, clock, _ = rig
    start = clock.now
    with tr.phase("outer"):
        clock.tick(5)
        with tr.phase("inner"):
            clock.tick(70)
            with tr.phase("innermost"):
                clock.tick(11)
            clock.tick(2)
        clock.tick(3)
        with tr.phase("inner"):
            clock.tick(7)
    wall = clock.now - start
    assert _total_ns(tr, "outer") == 8
    assert _total_ns(tr, "inner") == 79
    assert tr.registry.timer("phase.inner").count == 2
    assert _total_ns(tr, "innermost") == 11
    # the sum of the self times IS the outer wall: nothing counted twice
    assert sum(_total_ns(tr, n) for n in ("outer", "inner", "innermost")) \
        == wall == 98


def test_running_phase_is_the_only_annotation_and_waits_are_never_one(rig):
    tr, clock, notes = rig
    with tr.phase("outer"):
        with tr.phase("lock_wait", wait=True):
            clock.tick(40)
        with tr.phase("inner"):
            clock.tick(1)
    assert _total_ns(tr, "lock_wait") == 40  # timed all the same
    assert "lock_wait" not in {name for _, name in notes.events}
    # the outer one is closed while another phase runs and opened again
    # after: the segments of one thread never overlap
    assert notes.events == [
        ("enter", "outer"), ("exit", "outer"),      # suspended by the wait
        ("enter", "outer"), ("exit", "outer"),      # ... and by inner
        ("enter", "inner"), ("exit", "inner"),
        ("enter", "outer"), ("exit", "outer"),
    ]
    depth = 0
    for kind, _ in notes.events:
        depth += 1 if kind == "enter" else -1
        assert depth in (0, 1)


def test_phase_is_a_timed_child_and_never_the_current_span(rig):
    tr, clock, _ = rig
    with tr.span("request") as root:
        with tr.phase("evaluate", graph="g"):
            assert tr.current() is root  # annotations land where they did
            with tr.span("store.read"):
                clock.tick(9_000_000)
            clock.tick(1_000_000)
    assert [c.name for c in root.children] == ["store.read", "evaluate"]
    child = root.children[1]
    assert child.attrs == {"graph": "g", "self_ms": 10.0}  # no inner phase
    assert child.trace_id == root.trace_id
    assert child.duration_ms == 10.0
    # outside any span a phase is timed, not retained
    with tr.phase("read"):
        clock.tick(4)
    assert _total_ns(tr, "read") == 4
    assert [r.name for r in tr.recent()] == ["request"]


def test_phase_survives_an_exception_and_resumes_the_outer(rig):
    tr, clock, notes = rig
    with tr.phase("outer"):
        with pytest.raises(ValueError):
            with tr.phase("inner"):
                clock.tick(6)
                raise ValueError("boom")
        clock.tick(2)
    assert (_total_ns(tr, "inner"), _total_ns(tr, "outer")) == (6, 2)
    assert notes.events[-2:] == [("enter", "outer"), ("exit", "outer")]
    assert tr._phase_stack() == []


def test_threads_keep_their_own_phase_stacks(rig):
    tr, clock, notes = rig
    inside, release = threading.Event(), threading.Event()

    def worker():
        with tr.phase("worker"):
            inside.set()
            assert release.wait(10)

    th = threading.Thread(target=worker)
    with tr.phase("main"):
        th.start()
        assert inside.wait(10)
        # another thread's phase suspended nothing here
        assert notes.events == [("enter", "main"), ("enter", "worker")]
        clock.tick(5)
        release.set()
        th.join(10)
    assert not th.is_alive()
    assert _total_ns(tr, "main") == 5


def test_without_jax_loaded_there_is_no_annotation(monkeypatch):
    import sys

    tr = Tracer()
    tr.registry = TelemetryRegistry()
    monkeypatch.setitem(sys.modules, "jax", None)
    with tr.phase("quiet"):
        pass
    assert tr.annotation is None
    assert tr.registry.timer("phase.quiet").count == 1


def test_default_annotation_is_the_profilers():
    import jax

    with tracer.phase("test.annotated"):
        pass
    assert tracer.annotation is jax.profiler.TraceAnnotation


# ------------------------------------------------------------- the sites

def _wait_for(predicate, what, seconds=10.0):
    deadline = time.monotonic() + seconds
    while not predicate():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


def test_spilled_request_moves_every_served_phase_once():
    """One spilled request over HTTP: each phase of the table once
    (`server.serialize` at its two sites, `executor.dispatch` once per
    superstep, `executor.sync` once more for the result), and together
    they tile `server.request.wall`."""
    from test_spillover import _social_graph

    from janusgraph_tpu.server.manager import JanusGraphManager
    from janusgraph_tpu.server.server import JanusGraphServer

    g, people, _ = _social_graph()
    mgr = JanusGraphManager()
    mgr.put_graph("graph", g)
    server = JanusGraphServer(manager=mgr).start()

    def post(query):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/gremlin",
            data=json.dumps({"gremlin": query}).encode(),
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def serialized():
        return registry.snapshot().get(
            "phase.server.serialize", {}).get("count", 0)

    try:
        query = (f"g.V({people[0]}).out('knows').out('knows')"
                 ".dedup().count()")
        at_start = serialized()
        for _ in range(2):  # teach the shape, then spill it once (compiles)
            answer = post(query)["result"]
        # the handler leaves its last phase after the client has the body
        _wait_for(lambda: serialized() == at_start + 4, "two requests' ends")
        before = registry.snapshot()
        spilled_before = before["olap.spillover.spilled"]["count"]
        assert post(query)["result"] == answer
        _wait_for(lambda: serialized() == at_start + 6, "the request's end")
        after = registry.snapshot()
        assert after["olap.spillover.spilled"]["count"] == spilled_before + 1
        supersteps = registry.last_run("olap")["supersteps"]
        assert supersteps == 1  # hop 0 is read off the seed's row on the host
        expected = {name: 1 for name in SERVER_PHASES + SPILL_PHASES
                    + EXECUTOR_PHASES}
        expected["server.serialize"] = 2  # the result, then the response
        expected["executor.dispatch"] = supersteps
        expected["executor.sync"] = supersteps + 1  # ... and the result
        for name, count in expected.items():
            assert _moved(before, name) == count, name
        assert _moved(before, "executor.tier") == 0  # the frontier's alone
        # tiling: what ran under server.request.wall is evaluate, the
        # spill and executor phases and the result's serialization; the
        # rest of the wall is unphased glue, never negative
        wall_ms = (after["server.request.wall"]["total_ms"]
                   - before["server.request.wall"]["total_ms"])
        inside = sum(
            _moved(before, name, "total_ms")
            for name in ("server.evaluate",) + SPILL_PHASES
            + EXECUTOR_PHASES + ("executor.sync",)
        )
        assert 0 < inside <= wall_ms + _moved(
            before, "server.serialize", "total_ms")
        root = [r for r in tracer.recent("server.request")][-1]
        names = {c.name for c in root.children}
        assert {"server.evaluate", "spill.lock_wait", "spill.plan"} <= names
        assert root.find("executor.publish")
    finally:
        server.stop()
        g.close()


def test_no_phase_boundary_between_the_planners_two_lock_takes(monkeypatch):
    """The planner takes its lock for the promotion check and again, at
    once, for the plan; which waiter gets it in between decides the served
    median (PERF.md, PR 25: +35% from a few microseconds there). So the
    wait phase opens before the first take and closes under the second,
    and no phase begins or ends between them."""
    from test_spillover import _social_graph

    g, people, _ = _social_graph()
    try:
        planner = g.spillover_planner

        def build():
            return g.traversal().V(people[0]).out("knows").out("knows")

        build().count()  # teach the shape
        spilled = registry.snapshot()["olap.spillover.spilled"]["count"]
        events = []

        class Recording:
            def __init__(self, lock):
                self._lock = lock

            def __enter__(self):
                self._lock.acquire()
                events.append("take")

            def __exit__(self, *exc):
                events.append("release")
                self._lock.release()

        clock = tracer._clock
        monkeypatch.setattr(planner, "_lock", Recording(planner._lock))
        # every phase boundary reads the tracer's clock exactly once
        monkeypatch.setattr(
            tracer, "_clock", lambda: (events.append("phase"), clock())[1])
        build().count()
        assert (registry.snapshot()["olap.spillover.spilled"]["count"]
                == spilled + 1)
        check_done = events.index("release")
        plan_taken = events.index("take", check_done)
        assert events[:check_done].count("take") == 1
        assert "phase" not in events[check_done:plan_taken]
        # spill.recognize out and spill.lock_wait in before the first take;
        # lock_wait out, spill.plan in right after the second
        assert events[:events.index("take")].count("phase") >= 2
        assert events[plan_taken + 1:plan_taken + 3] == ["phase", "phase"]
    finally:
        g.close()


def test_row_path_request_pays_for_no_spill_phase():
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({"schema.default": "auto", "computer.spillover": True})
    try:
        tx = g.new_transaction()
        a, b = tx.add_vertex("person"), tx.add_vertex("person")
        tx.add_edge(a, "knows", b)
        tx.commit()
        before = registry.snapshot()
        assert g.traversal().V(a.id).out("knows").count() == 1
        for name in SPILL_PHASES:
            assert _moved(before, name) == 0, name
    finally:
        g.close()


def _random_csr(n=200, m=900, seed=5):
    from janusgraph_tpu.olap import csr_from_edges

    rng = np.random.default_rng(seed)
    return csr_from_edges(
        n, rng.integers(0, n, m).astype(np.int32),
        rng.integers(0, n, m).astype(np.int32),
    )


def _run_fused(ex):
    from janusgraph_tpu.olap.programs import PageRankProgram

    ex.run(PageRankProgram(max_iterations=3, tol=0.0))
    return {"executor.dispatch": 1, "executor.sync": 1}


def _run_host_loop(ex):
    from janusgraph_tpu.olap.programs import PageRankProgram

    ex.run(PageRankProgram(max_iterations=3, tol=0.0), fused=False)
    # a wait per superstep's aggregators, and one for the result
    return {"executor.dispatch": 3, "executor.sync": 4}


def _run_frontier(ex):
    from janusgraph_tpu.olap.programs import ShortestPathProgram

    ex.run(ShortestPathProgram(seed_index=0, max_iterations=3),
           frontier="always")
    hops = ex.last_run_info["supersteps"]
    assert hops >= 1
    # every hop: one tier choice with the wait for its plan inside it and
    # one step; then the wait for the last step
    tiers = hops if hops == 3 else hops + 1  # a hop that found nothing
    return {"executor.tier": tiers, "executor.dispatch": hops,
            "executor.sync": tiers + 1}


@pytest.mark.parametrize("path,drive", [
    ("fused", _run_fused), ("host-loop", _run_host_loop),
    ("frontier", _run_frontier),
])
def test_every_executor_path_moves_its_phases(path, drive):
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    ex = TPUExecutor(_random_csr())
    before = registry.snapshot()
    expected = {"executor.setup": 1, "executor.fetch": 1,
                "executor.publish": 1, **drive(ex)}
    assert ex.last_run_info["path"] == path
    for name, count in expected.items():
        assert _moved(before, name) == count, name
    if path != "frontier":
        assert _moved(before, "executor.tier") == 0
    run = tracer.recent("olap.run")[-1]
    assert {"executor.setup", "executor.fetch", "executor.publish"} <= {
        c.name for c in run.children}


def test_program_counts_backend_compiles_not_calls():
    import jax
    import jax.numpy as jnp

    from janusgraph_tpu.olap.device import count_compiles

    count_compiles()
    count_compiles()  # registering twice must not count twice

    def compiles():
        return registry.snapshot().get(
            "jax.compile.backend", {"count": 0, "total_ms": 0.0})

    x = jnp.arange(37, dtype=jnp.float32)  # its own executables, first
    fn = jax.jit(lambda v: (v * 3.0 + 1.0).sum())
    before = compiles()
    fn(x).block_until_ready()
    first = compiles()
    assert first["count"] == before["count"] + 1
    assert first["total_ms"] > before["total_ms"]
    fn(x).block_until_ready()
    assert compiles()["count"] == first["count"]  # a call, not a compile
    fn(jnp.arange(41, dtype=jnp.float32)).block_until_ready()
    assert compiles()["count"] >= first["count"] + 1  # a new shape
