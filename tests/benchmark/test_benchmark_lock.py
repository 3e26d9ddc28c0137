"""The per-layer metrics that read the spillover planner's lock ledger and
the wall of the phases its holder runs (benchmark/readers/lock.py and a
reader that was there): the metric files, the new reader's arithmetic on a
synthetic registry, what a program without the ledger gives them to read,
and the served cell's traced CPU rehearsal."""

import json
import math

import pytest

from rehearsal import REPO, over, rehearse
from test_benchmark_phases import _readers, _run, _timer

import run as harness  # noqa: E402  (rehearsal puts benchmark/ on the path)

LOCK_METRICS = [
    "lock_held_wall_ms", "lock_handoff_ms", "lock_free_ms",
    "lock_queue_depth", "lock_overtake_share",
]
#: what the holder of the planner's lock runs while the device has nothing
LOCK_HELD_PHASES = [
    "spill.plan", "executor.setup", "executor.dispatch", "executor.tier",
    "executor.fetch", "executor.publish", "spill.reduce", "spill.publish",
]


def _counter(count):
    return {"type": "counter", "count": count}


@pytest.mark.parametrize("view,name", over(lambda view: LOCK_METRICS))
def test_metric_file_is_declared_and_names_a_registered_reader(view, name):
    catalog = view.catalog
    metric = json.load(open(catalog.find("layer_metrics", name + ".json")))
    entry = view.entry("per_layer", name)
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert metric[key] == entry[key], key
    assert metric["reader"] in catalog.plugins("readers", "READERS")
    assert (metric["layer"], metric["moves"], metric["better"]) == (
        "spillover", "request_p50_ms", "lower")
    # the served cells report it, and no other kind of cell
    cells = [c for c in view.cells if harness.reports(entry, c)]
    assert cells and all(view.kind_of(c) == "served-store" for c in cells)
    for cell in view.cells:
        loaded = {m["name"] for m in view.cell(cell)["layer_metrics"]}
        assert (name in loaded) == (cell in cells), cell


def test_lock_held_wall_reads_the_host_phases_under_the_lock_not_the_waits():
    metric = json.load(open(harness.Catalog([REPO]).find(
        "layer_metrics", "lock_held_wall_ms.json")))
    assert metric["args"]["timers"] == ["phase." + p for p in LOCK_HELD_PHASES]
    # not the wait on the device, the wait for the lock, or what runs
    # before the lock is taken
    assert not {"executor.sync", "spill.lock_wait", "spill.recognize"} & set(
        LOCK_HELD_PHASES)
    read = _readers()[metric["reader"]]
    before = {"phase.spill.plan": _timer(2, 10.0),
              "phase.executor.fetch": _timer(2, 50.0)}
    after = {"phase.spill.plan": _timer(12, 40.0),
             "phase.executor.fetch": _timer(12, 75.0),
             "phase.executor.sync": _timer(20, 300.0),
             "phase.spill.lock_wait": _timer(10, 570.0)}
    # moved, not cumulative: (40 - 10) + (75 - 50) over 10 requests
    assert read(_run(before, after, {"requests": 10}),
                **metric["args"]) == pytest.approx(5.5)


def test_counter_per_divides_what_two_counters_moved():
    read = _readers()["registry-counter-per"]
    before = {"olap.spillover.lock.waiters_seen": _counter(7),
              "olap.spillover.lock.overtakes": _counter(3),
              "olap.spillover.spilled": _counter(11)}
    after = {"olap.spillover.lock.waiters_seen": _counter(507),
             "olap.spillover.lock.overtakes": _counter(123),
             "olap.spillover.spilled": _counter(211)}
    run = _run(before, after)
    assert read(run, counter="olap.spillover.lock.waiters_seen",
                per_counter="olap.spillover.spilled") == pytest.approx(2.5)
    assert read(run, counter="olap.spillover.lock.overtakes",
                per_counter="olap.spillover.spilled",
                scale=100.0) == pytest.approx(60.0)
    # a window in which nothing spilled has nothing to divide by
    assert read(_run(after, after),
                counter="olap.spillover.lock.overtakes",
                per_counter="olap.spillover.spilled") is None


def test_a_program_without_the_ledger_gives_its_readers_nothing():
    """The parent of the PR that added the lock's ledger runs these files
    too: it has phases and the spilled counter and none of the new names,
    so it reports the lock-held wall and nothing of the ledger."""
    readers = _readers()
    old = {"phase.spill.plan": _timer(10, 30.0),
           "phase.executor.setup": _timer(10, 10.0),
           "phase.spill.lock_wait": _timer(10, 570.0),
           "olap.spillover.spilled": _counter(10)}
    newer = {**old, "phase.spill.plan": _timer(20, 50.0),
             "olap.spillover.spilled": _counter(20)}
    for name in LOCK_METRICS:
        metric = json.load(open(harness.Catalog([REPO]).find(
            "layer_metrics", name + ".json")))
        got = readers[metric["reader"]](
            _run(old, newer, {"requests": 10}), **metric["args"])
        if name == "lock_held_wall_ms":
            assert got == pytest.approx(2.0)
        else:
            assert got is None, name
        assert readers[metric["reader"]](_run(), **metric["args"]) is None


def test_traced_rehearsal_reports_the_lock_metrics(tmp_path):
    line, notes, _ = rehearse("g500-served.twohop", tmp_path, trace=1,
                              seed=2**31 + 36)
    assert line["correct"] is True
    for name in LOCK_METRICS:
        value = line["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, name
    assert line["metrics"]["lock_held_wall_ms"]["value"] > 0
    # every acquisition is a hand-off or found the lock free
    assert (line["metrics"]["lock_handoff_ms"]["value"]
            + line["metrics"]["lock_free_ms"]["value"]) > 0
    assert line["metrics"]["lock_overtake_share"]["value"] <= 100.0
    clients = notes["counts"]["max_outstanding"]
    assert line["metrics"]["lock_queue_depth"]["value"] <= clients - 1
    # the lock-held wall is the two host metrics that were there, less
    # what runs before the lock is taken
    phases = notes["notes"]["phases"]
    requests = notes["counts"]["requests"]
    assert line["metrics"]["lock_held_wall_ms"]["value"] == pytest.approx(
        line["metrics"]["executor_host_ms.served"]["value"]
        + line["metrics"]["spill_host_ms"]["value"]
        - phases["spill.recognize"]["total_ms"] / requests)
    # the run record carries the lock's fields
    record = notes["notes"]["run_info"]["spillover"]
    assert {"queue_depth", "overtook"} <= set(record)
    assert not {"lock_wait_ms", "handoff_ms"} & set(record)
