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


# ------------------------------------------------------- the thread's CPU clock

@pytest.fixture
def cpu_rig():
    """A tracer on two injected clocks: wall, and the thread's CPU."""
    clock, cpu = FakeClock(), FakeClock()
    tr = Tracer(clock=clock, cpu_clock=cpu)
    tr.registry = TelemetryRegistry()
    tr.annotation = Annotations()

    def run(wall_ns, cpu_ns):
        clock.tick(wall_ns)
        cpu.tick(cpu_ns)

    return tr, run


def _cpu_ns(tr, name):
    return tr.registry.timer("phase_cpu." + name).total_ns


def test_self_cpu_time_tiles_like_self_wall_time(cpu_rig):
    tr, run = cpu_rig
    ms = 1_000_000
    with tr.phase("outer"):
        run(5 * ms, 4 * ms)
        with tr.phase("inner"):
            run(70 * ms, 10 * ms)  # 60 off the CPU: runnable, not running
            with tr.phase("innermost", wait=True):
                run(11 * ms, 0)  # a wait keeps CPU time too, and reads none
            run(2 * ms, 2 * ms)
        run(3 * ms, 1 * ms)
        with tr.phase("inner"):
            run(7 * ms, 7 * ms)
    assert [_cpu_ns(tr, n) for n in ("outer", "inner", "innermost")] \
        == [5 * ms, 19 * ms, 0]
    assert [_total_ns(tr, n) for n in ("outer", "inner", "innermost")] \
        == [8 * ms, 79 * ms, 11 * ms]  # the wall clock's side, as before
    # one observation of CPU time for each of wall time
    for name in ("outer", "inner", "innermost"):
        assert (tr.registry.timer("phase_cpu." + name).count
                == tr.registry.timer("phase." + name).count)
    # the self CPU times sum to the CPU time of the outer one's stretch
    assert sum(_cpu_ns(tr, n)
               for n in ("outer", "inner", "innermost")) == 24 * ms


def test_a_boundary_reads_the_cpu_clock_once_or_records_nothing():
    """Every boundary of a tracer with a CPU clock reads it, once; a phase
    with a boundary that had none to read (the clock was switched on or
    off while it was open) records no CPU time at all, never an estimate."""
    wall, cpu, reads = FakeClock(), FakeClock(), []

    def cpu_clock():
        reads.append(1)
        return cpu.now

    def run(wall_ns, cpu_ns):
        wall.tick(wall_ns)
        cpu.tick(cpu_ns)

    tr = Tracer(clock=wall)
    tr.registry = TelemetryRegistry()
    tr.annotation = Annotations()
    with tr.phase("outer"):                 # no clock yet: nothing read
        run(10, 10)
        tr.cpu_clock = cpu_clock
        with tr.phase("inner"):             # enter and exit read
            run(20, 5)
            with tr.phase("innermost"):
                run(30, 30)
            run(5, 5)
        assert len(reads) == 4
        run(7, 7)
    assert len(reads) == 5                  # outer's exit
    with tr.phase("later"):
        run(3, 2)
        tr.cpu_clock = None                 # switched off: no reading to end on
    assert len(reads) == 6
    assert [_cpu_ns(tr, n) for n in ("inner", "innermost")] == [10, 30]
    snapshot = tr.registry.snapshot()
    assert "phase_cpu.outer" not in snapshot
    assert "phase_cpu.later" not in snapshot
    # the wall clock's side is whole
    assert [_total_ns(tr, n) for n in ("outer", "inner", "innermost",
                                        "later")] == [17, 25, 30, 3]


def test_cpu_time_survives_an_exception_and_resumes_the_outer(cpu_rig):
    tr, run = cpu_rig
    with tr.phase("outer"):
        with pytest.raises(ValueError):
            with tr.phase("inner"):
                run(6_000_000, 3_000_000)
                raise ValueError("boom")
        run(2_000_000, 2_000_000)
    assert (_cpu_ns(tr, "inner"), _cpu_ns(tr, "outer")) \
        == (3_000_000, 2_000_000)


def test_cpu_time_is_the_calling_threads_own():
    """The CPU clock is per thread (CLOCK_THREAD_CPUTIME_ID): each phase
    reads the clock of the thread it runs on, and another thread's phase
    suspends nothing here."""
    wall = FakeClock()
    cpus = {}  # thread name -> its CPU clock

    def cpu_clock():
        return cpus[threading.current_thread().name].now

    tr = Tracer(clock=wall, cpu_clock=cpu_clock)
    tr.registry = TelemetryRegistry()
    tr.annotation = Annotations()
    cpus[threading.current_thread().name] = main_cpu = FakeClock()
    cpus["worker"] = worker_cpu = FakeClock()
    worker_cpu.now = 5_000_000  # the clocks of two threads share no origin
    inside, release = threading.Event(), threading.Event()

    def worker():
        with tr.phase("worker"):
            inside.set()
            assert release.wait(10)

    th = threading.Thread(target=worker, name="worker")
    with tr.phase("main"):
        th.start()
        assert inside.wait(10)
        main_cpu.tick(5_000_000)
        worker_cpu.tick(90_000_000)
        wall.tick(100_000_000)
        release.set()
        th.join(10)
    assert not th.is_alive()
    assert (_cpu_ns(tr, "main"), _cpu_ns(tr, "worker")) \
        == (5_000_000, 90_000_000)
    assert (_total_ns(tr, "main"), _total_ns(tr, "worker")) \
        == (100_000_000, 100_000_000)


def test_phase_child_carries_cpu_ms_beside_self_ms(cpu_rig):
    tr, run = cpu_rig
    with tr.span("request") as root:
        with tr.phase("evaluate"):
            run(10_000_000, 2_500_000)
    assert root.children[0].attrs == {"self_ms": 10.0, "cpu_ms": 2.5}


def test_a_tracer_keeps_no_cpu_time_unless_it_is_given_the_clock(rig):
    tr, clock, _ = rig
    with tr.span("request") as root:
        with tr.phase("evaluate"):
            clock.tick(1_000_000)
    assert "cpu_ms" not in root.children[0].attrs
    assert "phase_cpu.evaluate" not in tr.registry.snapshot()
    # nor does the process tracer: where the clock is a dear system call
    # that moves in steps of 10 ms (gVisor), it would cost and say nothing
    assert tracer.cpu_clock is None


def test_a_busy_loop_reads_cpu_near_wall_and_a_sleep_near_none():
    tr = Tracer(cpu_clock=time.thread_time_ns)
    tr.registry = TelemetryRegistry()
    busy_until = time.thread_time_ns() + 20_000_000
    with tr.phase("busy"):
        while time.thread_time_ns() < busy_until:
            pass
    with tr.phase("asleep", wait=True):
        time.sleep(0.05)
    busy_cpu, busy_wall = _cpu_ns(tr, "busy"), _total_ns(tr, "busy")
    assert 19_000_000 <= busy_cpu <= busy_wall + 1_000_000
    # asleep, the thread ran for the call and the wake-up alone
    assert _total_ns(tr, "asleep") >= 45_000_000
    assert _cpu_ns(tr, "asleep") < 5_000_000


def test_phase_cpu_moves_once_per_phase_on_every_executor_path(monkeypatch):
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    monkeypatch.setattr(tracer, "cpu_clock", time.thread_time_ns)
    ex = TPUExecutor(_random_csr())
    before = registry.snapshot()
    _run_host_loop(ex)
    after = registry.snapshot()
    moved = {
        name[len("phase."):]: entry["count"] - before.get(
            name, {}).get("count", 0)
        for name, entry in after.items() if name.startswith("phase.")
    }
    moved = {name: count for name, count in moved.items() if count}
    assert {"executor.setup", "executor.dispatch", "executor.sync",
            "executor.fetch", "executor.publish"} <= set(moved)
    for name, count in moved.items():
        cpu = "phase_cpu." + name
        assert (after[cpu]["count"]
                - before.get(cpu, {}).get("count", 0)) == count, name
        # a thread cannot have run for longer than it was there
        assert (after[cpu]["total_ms"] - before.get(cpu, {}).get(
            "total_ms", 0.0)) <= _moved(before, name, "total_ms") + 1.0, name
    run = tracer.recent("olap.run")[-1]
    child = [c for c in run.children if c.name == "executor.setup"][0]
    assert 0 <= child.attrs["cpu_ms"] <= child.attrs["self_ms"] + 1.0


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
        expected["spill.plan"] = 2  # its own plan, then the dispatch it led
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


def test_only_the_plan_stands_between_the_planners_two_lock_takes(monkeypatch):
    """A request takes the planner's short lock for its check and then the
    device's lock to dispatch; which waiter gets that one decides the
    served median (PERF.md, PR 25: +35% from a few microseconds there).
    The ONE wait phase opens before the first take and closes under the
    second; between the takes runs the
    request's own plan (one phase, which suspends the wait) and no other
    boundary, and none between the plan's end and the second take, where
    the request stands for a dispatch."""
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
            def __init__(self, lock, which=""):
                self._lock, self._which = lock, which

            def __enter__(self):
                self._lock.acquire()
                events.append("take" + self._which)

            def __exit__(self, *exc):
                events.append("release" + self._which)
                self._lock.release()

        clock = tracer._clock
        waits = registry.snapshot()["phase.spill.lock_wait"]["count"]
        monkeypatch.setattr(planner, "_lock", Recording(planner._lock))
        monkeypatch.setattr(
            planner, "_state", Recording(planner._state, ":state"))
        # every phase boundary reads the tracer's clock exactly once
        monkeypatch.setattr(
            tracer, "_clock", lambda: (events.append("phase"), clock())[1])
        build().count()
        assert (registry.snapshot()["olap.spillover.spilled"]["count"]
                == spilled + 1)
        check_done = events.index("release:state")
        plan_taken = events.index("take")
        assert events[:check_done].count("take:state") == 1
        # spill.plan in and out, in the request's own thread
        assert events[check_done + 1:plan_taken] == ["phase", "phase"]
        # spill.recognize out and spill.lock_wait in before the first take;
        # lock_wait out, the holder's spill.plan in right after the second
        assert events[:events.index("take:state")].count("phase") >= 2
        assert events[plan_taken + 1:plan_taken + 3] == ["phase", "phase"]
        # one wait phase a request, whatever it waited for
        assert registry.snapshot()["phase.spill.lock_wait"]["count"] == (
            waits + 1)
    finally:
        g.close()


def test_nothing_is_written_or_read_where_a_request_stands_at_the_lock(
        monkeypatch):
    """The lock's ledger (tickets, queue depth, hand-off) and the phases'
    CPU clock keep to the rule of the test above: from the end of the
    request's own plan to the second take no clock is read, wall or CPU,
    the registry is not touched and the planner stores no attribute (the
    request enters `_pending`, an item of a dict, and stands); the ledger
    is written under the take that dispatches, without a clock of its
    own, and the release stamp is the last thing under the lock."""
    from test_spillover import _social_graph

    g, people, _ = _social_graph()
    try:
        planner = g.spillover_planner

        def build():
            return g.traversal().V(people[0]).out("knows").out("knows")

        build().count()  # teach the shape
        build().count()  # ... and leave a release stamp to hold against
        spilled = registry.snapshot()["olap.spillover.spilled"]["count"]
        events = []

        class Recording:
            def __init__(self, lock, which=""):
                self._lock, self._which = lock, which

            def __enter__(self):
                self._lock.acquire()
                events.append("take" + self._which)

            def __exit__(self, *exc):
                events.append("release" + self._which)
                self._lock.release()

        class Watched(type(planner)):
            def __setattr__(self, name, value):
                events.append("store:" + name)
                super().__setattr__(name, value)

        def noting(what, fn):
            def noted(*args, **kwargs):
                events.append(what)
                return fn(*args, **kwargs)
            return noted

        monkeypatch.setattr(planner, "_lock", Recording(planner._lock))
        monkeypatch.setattr(
            planner, "_state", Recording(planner._state, ":state"))
        monkeypatch.setattr(tracer, "_clock", noting("clock", tracer._clock))
        monkeypatch.setattr(
            tracer, "cpu_clock", noting("cpu", time.thread_time_ns))
        for accessor in ("counter", "timer", "histogram", "gauge",
                         "set_gauge", "record_run"):
            monkeypatch.setattr(registry, accessor, noting(
                "registry:" + accessor, getattr(registry, accessor)))
        planner.__class__ = Watched
        try:
            build().count()
        finally:
            planner.__class__ = Watched.__bases__[0]
        assert (registry.snapshot()["olap.spillover.spilled"]["count"]
                == spilled + 1)
        check_done = events.index("release:state")
        plan_taken = events.index("take")
        assert events[:check_done].count("take:state") == 1
        # between the takes, the request's own plan: its phase opens on one
        # read of each clock and closes on one, writing its two timers, and
        # THEN NOTHING until the lock is held
        assert events[check_done + 1:plan_taken] == [
            "clock", "cpu", "clock", "cpu",
            "registry:timer", "registry:timer"]
        # under the second take: the wait phase closes on one read of each
        # clock and writes its wall and CPU, the holder's spill.plan opens,
        # and up to the dispatch's span the ledger and the freshness check
        # write the registry without reading a clock
        held = events[plan_taken + 1:events.index("release", plan_taken)]
        assert held[:6] == ["clock", "cpu", "registry:timer",
                            "registry:timer", "clock", "cpu"]
        ledger = held[6:held.index("clock", 6)]
        assert ledger.count("registry:timer") == 2  # hand-off and free time
        assert ledger[0] == "take:state"  # the freshness check, first
        assert all(e.startswith("registry:") or e.endswith(":state")
                   for e in ledger)
        # the release stamp is the last thing under the lock
        assert held[-2:] == ["clock", "store:_released_ns"]
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
