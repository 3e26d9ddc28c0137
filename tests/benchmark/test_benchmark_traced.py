"""The traced run of benchmark/run.py in its CPU rehearsal: per-layer
metrics only, device readers that find nothing report nothing, the served
cell is promoted and its loop stays closed."""

import json
import os

import pytest

from rehearsal import REPO, over_cells, rehearse


@pytest.mark.parametrize("view,cell", over_cells())
def test_traced_rehearsal_reports_layer_metrics_only(view, cell, tmp_path):
    line, notes, _ = rehearse(cell, tmp_path, trace=1, view=view)
    assert line["correct"] is True and line["failed"] == 0
    reported = set(line["metrics"])
    # the CPU's trace has no device plane: readers of the device trace
    # find nothing and their metrics are left out, not invented
    assert reported and reported <= view.declared("per_layer", cell)
    assert not reported & view.declared("end_to_end", cell)
    from_the_device_trace = {
        m["name"] for m in view.manifest["per_layer"]
        if m["source"] == "device_trace"}
    assert not reported & from_the_device_trace
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert {"setup_snapshot_s", "setup_cache_misses"} <= reported
    assert notes["spans"]["traced"] > 0
    assert not os.path.exists(os.path.join(
        tmp_path, "out", f"{cell}.seed{2**31 + 11}.trace1", "trace"))


def test_served_rehearsal_promotes_and_keeps_the_loop_closed(tmp_path):
    line, notes, lines = rehearse("g500-served.twohop", tmp_path, trace=1)
    assert any("spilled" in ln and "promotion attempt" in ln for ln in lines)
    assert line["metrics"]["spilled_share"]["value"] == 100.0
    assert line["metrics"]["server_request_ms"]["value"] > 0
    # the tail, which spreads too widely for a bound, is a layer metric
    assert (line["metrics"]["request_p95_layer_ms"]["value"]
            == notes["end_to_end"]["request_p95_ms"])
    assert notes["notes"]["run_info"]["path"] == "host-loop"
    clients = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "twohop-closed.json")))["clients"]
    assert 1 <= notes["counts"]["max_outstanding"] <= clients
