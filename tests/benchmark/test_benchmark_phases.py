"""The per-layer metrics that read the program's own phases and compile
timer (benchmark/readers/phases.py): the metric files, the readers'
arithmetic on a synthetic run, that a program without phases gives them
nothing to read, and each cell's traced CPU rehearsal."""

import json
import math
import os
import types

import pytest

from rehearsal import MANIFEST, REPO, declared, rehearse

import run as harness  # noqa: E402  (rehearsal puts benchmark/ on the path)

NEW_METRICS = {
    "spill_lock_wait_ms": ["g500-served.twohop"],
    "spill_host_ms": ["g500-served.twohop"],
    "executor_host_ms.served": ["g500-served.twohop"],
    "executor_host_ms.olap": ["g500-olap.pagerank", "g500-olap.bfs"],
    "server_host_ms": ["g500-served.twohop"],
    "idle_unnamed_share.served": ["g500-served.twohop"],
    "idle_unnamed_share.olap": ["g500-olap.pagerank", "g500-olap.bfs"],
    "setup_compile_s": [w["name"] for w in MANIFEST["workloads"]],
}
#: those whose reader needs the device plane of a trace: silent on the CPU
NEEDS_DEVICE = {"idle_unnamed_share.served", "idle_unnamed_share.olap"}


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


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_metric_file_is_declared_and_names_a_registered_reader(name):
    metric = json.load(open(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".json")))
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert metric[key] == entry[key], key
    assert metric["reader"] in _readers()
    assert metric["better"] == "lower"
    cells = entry.get("workloads", NEW_METRICS["setup_compile_s"])
    assert cells == NEW_METRICS[name]
    # the cells it lists are the cells whose configuration's kind it reads
    catalog = harness.Catalog([REPO])
    for cell in NEW_METRICS["setup_compile_s"]:
        loaded = {m["name"] for m in catalog.cell(cell)["layer_metrics"]}
        assert (name in loaded) == (cell in cells), cell


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


@pytest.mark.parametrize("cell", NEW_METRICS["setup_compile_s"])
def test_traced_rehearsal_reports_the_phase_metrics(cell, tmp_path):
    line, notes, _ = rehearse(cell, tmp_path, trace=1, seed=2**31 + 29)
    assert line["correct"] is True
    expected = {name for name, cells in NEW_METRICS.items()
                if cell in cells} - NEEDS_DEVICE
    assert expected <= declared("per_layer", cell)
    for name in expected:
        value = line["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, name
    assert not NEEDS_DEVICE & set(line["metrics"])  # no device plane here
    assert line["metrics"]["setup_compile_s"]["value"] > 0
    phases = notes["notes"]["phases"]
    assert {"executor.setup", "executor.dispatch", "executor.fetch",
            "executor.publish"} <= set(phases)
    if cell == "g500-served.twohop":
        assert line["metrics"]["executor_host_ms.served"]["value"] > 0
        assert line["metrics"]["spill_host_ms"]["value"] > 0
        assert line["metrics"]["server_host_ms"]["value"] > 0
        requests = notes["counts"]["requests"]
        assert phases["spill.lock_wait"]["count"] == requests
        assert phases["executor.dispatch"]["count"] == 2 * requests
    else:
        assert line["metrics"]["executor_host_ms.olap"]["value"] > 0
        assert ("executor.tier" in phases) == (cell == "g500-olap.bfs")
