"""benchmark/run.py end to end, as far as a machine without a chip can
check it: no result without a TPU, the CPU rehearsal of every cell walks
the whole control flow with 0 failed and marks every line, and a cell
added purely as data runs."""

import os
import shutil

import pytest

from rehearsal import (MANIFEST, POINT_CELL, REPO, over_cells, rehearse,
                       run_benchmark)


def test_no_result_without_a_chip(tmp_path):
    res = run_benchmark(["--workload", "g500-olap.pagerank", "--seed", "1",
                "--seconds", "1", "--trace", "0"], tmp_path)
    assert res.returncode != 0
    assert "JAX found platform 'cpu'" in res.stderr
    assert '"correct"' not in res.stdout


def test_no_result_for_a_cell_that_is_not_there(tmp_path):
    res = run_benchmark(["--workload", "g500-olap.nothing", "--cpu-rehearsal"],
               tmp_path)
    assert res.returncode != 0 and "no workloads entry" in res.stderr
    assert '"correct"' not in res.stdout


def test_no_result_in_a_directory_that_holds_only_the_benchmark(tmp_path):
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), bare)
    for path in MANIFEST["paths"]:
        shutil.copytree(os.path.join(REPO, path), bare / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = run_benchmark(["--workload", "g500-olap.pagerank", "--seed", "1",
                "--seconds", "1", "--trace", "0", "--cpu-rehearsal"],
               tmp_path, script=str(bare / "benchmark" / "run.py"),
               cwd=str(bare))
    assert res.returncode != 0
    assert "janusgraph_tpu" in res.stderr and '"correct"' not in res.stdout


@pytest.mark.parametrize("view,cell", over_cells())
def test_rehearsal_reports_the_cells_end_to_end_metrics(view, cell, tmp_path):
    line, notes, _ = rehearse(cell, tmp_path, view=view)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == view.declared("end_to_end", cell)
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                              "memory_peak_bytes": 0}
    assert notes["notes"]["admission_shed"] == 0
    assert notes["notes"]["spillover_fallbacks"] == {}
    assert notes["setup_s"] == line["metrics"]["setup_s"]["value"]
    assert notes["counts"]["requests"] == line["attempted"]


def test_a_cell_added_purely_as_data_runs(tmp_path):
    line, notes, _ = rehearse("g500-served.point", tmp_path,
                               extra=("--root", POINT_CELL))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 8
    assert {"request_p50_ms", "setup_s"} == set(line["metrics"])
    # 8 clients here, and never more requests outstanding than clients
    assert 1 <= notes["counts"]["max_outstanding"] <= 8
    # a 1-hop read is never promoted: the row path answered every one
    assert "run_info" not in notes["notes"]
