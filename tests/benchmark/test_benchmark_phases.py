"""The per-layer metrics that read the program's own phases and compile
timer (benchmark/readers/phases.py): the metric files, the readers'
arithmetic on a synthetic run, that a program without phases gives them
nothing to read, and each cell's traced CPU rehearsal."""

import json
import math
import os
import types

import pytest

from rehearsal import REPO, over, over_cells, rehearse

import run as harness  # noqa: E402  (rehearsal puts benchmark/ on the path)

#: the metrics this file is about; which cells report each is the
#: manifest's to say
PHASE_METRICS = [
    "spill_lock_wait_ms", "spill_host_ms", "executor_host_ms.served",
    "executor_host_ms.olap", "server_host_ms", "idle_unnamed_share.served",
    "idle_unnamed_share.olap", "setup_compile_s",
]
#: those whose reader needs the device plane of a trace: silent on the CPU
NEEDS_DEVICE = {"idle_unnamed_share.served", "idle_unnamed_share.olap"}
#: by the kind of a cell's configuration, the one that reads its host loop
EXECUTOR_HOST = {"olap-adopted": "executor_host_ms.olap",
                 "served-store": "executor_host_ms.served"}


def _readers():
    return harness.Catalog([REPO]).plugins("readers", "READERS")


def _run(before=None, after=None, counts=None, summary=None):
    """What a reader is handed, cut to what the phase readers touch."""
    run = types.SimpleNamespace(
        registry_before=before, registry_after=after, counts=counts or {},
        trace_summary=summary, notes={},
    )
    run.moved = types.MethodType(harness.Run.moved, run)
    return run


def _timer(count, total_ms):
    return {"type": "timer", "count": count, "total_ms": total_ms}


@pytest.mark.parametrize(
    "view,name", over(lambda view: sorted(PHASE_METRICS)))
def test_metric_file_is_declared_and_names_a_registered_reader(view, name):
    catalog = view.catalog
    metric = json.load(open(catalog.find("layer_metrics", name + ".json")))
    entry = view.entry("per_layer", name)
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert metric[key] == entry[key], key
    assert metric["reader"] in catalog.plugins("readers", "READERS")
    assert metric["better"] == "lower"
    # the rule of `Catalog.cell`, for every cell of the manifest: a cell
    # loads the metric when the entry lists it (or has no list); and no
    # entry lists a cell whose configuration's kind the file does not read
    for cell in view.cells:
        loaded = {m["name"] for m in view.cell(cell)["layer_metrics"]}
        assert (name in loaded) == harness.reports(entry, cell), cell
        if harness.reports(entry, cell):
            assert view.kind_of(cell) in metric["kinds"], cell
    assert any(harness.reports(entry, cell) for cell in view.cells)


def test_timers_per_sums_what_moved_in_the_window_and_notes_every_phase():
    read = _readers()["registry-timers-per"]
    before = {"phase.spill.plan": _timer(2, 10.0),
              "phase.spill.reduce": _timer(2, 4.0),
              "phase.executor.sync": _timer(4, 80.0)}
    after = {"phase.spill.plan": _timer(12, 40.0),
             "phase.spill.reduce": _timer(12, 9.0),
             "phase.executor.sync": _timer(24, 480.0),
             "phase.spill.publish": _timer(10, 1.0),
             "server.request.wall": _timer(10, 900.0)}
    run = _run(before, after, {"requests": 10})
    got = read(run, timers=["phase.spill.plan", "phase.spill.reduce",
                            "phase.spill.publish", "phase.spill.recognize"],
               per="requests")
    assert got == pytest.approx((30.0 + 5.0 + 1.0) / 10)
    # the per-phase table behind the sums goes to the notes, sync included
    assert run.notes["phases"]["executor.sync"] == {
        "count": 20, "total_ms": 400.0, "ms_per_request": 40.0}
    assert set(run.notes["phases"]) == {
        "spill.plan", "spill.reduce", "spill.publish", "executor.sync"}
    assert read(_run(before, after, {}), timers=["phase.spill.plan"],
                per="requests") is None  # nothing to divide by


def test_a_program_without_phases_gives_the_readers_nothing():
    """The parent of the PR that added phases runs these files too."""
    readers = _readers()
    old = {"server.request.wall": _timer(10, 900.0)}
    run = _run(old, old, {"requests": 10})
    assert readers["registry-timers-per"](
        run, timers=["phase.spill.lock_wait"], per="requests") is None
    assert readers["registry-timer-setup"](
        run, timer="jax.compile.backend") is None
    assert readers["idle-unnamed-share"](run) is None
    assert readers["registry-timers-per"](
        _run(), timers=["phase.spill.lock_wait"], per="requests") is None
    assert "phases" not in run.notes


def test_timer_setup_reads_what_had_accrued_when_the_window_opened():
    read = _readers()["registry-timer-setup"]
    run = _run({"jax.compile.backend": _timer(7, 31_250.0)},
               {"jax.compile.backend": _timer(9, 40_000.0)})
    assert read(run, timer="jax.compile.backend") == pytest.approx(31.25)


def test_idle_unnamed_share_on_a_synthetic_summary():
    read = _readers()["idle-unnamed-share"]
    summary = {"idle_gaps": [
        ["bench:request/executor.setup", 0.30],
        ["bench:request/python", 0.20],
        ["no_span/PjitFunction(multiply)", 0.05],
        ["device/in_program_bubble", 0.25],
        ["short_gaps/unattributed", 0.20],
    ]}
    assert read(_run(summary=summary)) == pytest.approx(25.0)
    named = {"idle_gaps": [["bench:submit/executor.fetch", 0.5]]}
    assert read(_run(summary=named)) == 0.0
    assert read(_run(summary={"idle_gaps": []})) is None


@pytest.mark.parametrize("view,cell", over_cells())
def test_traced_rehearsal_reports_the_phase_metrics(view, cell, tmp_path):
    line, notes, _ = rehearse(cell, tmp_path, trace=1, seed=2**31 + 29,
                              view=view)
    assert line["correct"] is True
    expected = (set(PHASE_METRICS) & view.declared("per_layer", cell)
                ) - NEEDS_DEVICE
    for name in expected:
        value = line["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, name
    assert not NEEDS_DEVICE & set(line["metrics"])  # no device plane here
    assert line["metrics"]["setup_compile_s"]["value"] > 0
    phases = notes["notes"].get("phases")
    if phases is None:
        # the table is the phase readers' note: a cell that no list of
        # theirs names has none
        assert expected <= {"setup_compile_s"}
        return
    kind, traffic = view.kind_of(cell), view.traffic_of(cell)
    requests = notes["counts"]["requests"]
    info = notes["notes"].get("run_info")
    if info:  # some request of the run reached the executor
        assert {"executor.setup", "executor.dispatch", "executor.fetch",
                "executor.publish"} <= set(phases)
        # a tier is chosen on the frontier engine's path alone
        assert ("executor.tier" in phases) == (info["path"] == "frontier")
        if EXECUTOR_HOST[kind] in expected:
            assert line["metrics"][EXECUTOR_HOST[kind]]["value"] > 0
    if kind == "served-store":
        for name in {"spill_host_ms", "server_host_ms"} & expected:
            assert line["metrics"][name]["value"] > 0
        templates = traffic["templates"]
        if all(t.get("promote") for t in templates):
            # every request spilled, and waited for the planner's lock once
            assert phases["spill.lock_wait"]["count"] == requests
            if len(templates) == 1:  # one superstep a hop
                hops = templates[0]["gremlin"].count(".out()")
                assert phases["executor.dispatch"]["count"] == hops * requests
    else:
        # an analyst's submit is one run of the executor
        assert phases["executor.publish"]["count"] == requests
