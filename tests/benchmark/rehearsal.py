"""Shared by the tests that drive benchmark/run.py as a subprocess: how a
run is started on a machine without a chip, and how a CPU rehearsal's
marked output is read."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmark", "run.py")
# the benchmark's modules import each other as top-level modules
sys.path.insert(0, os.path.join(REPO, "benchmark"))
POINT_CELL = os.path.join(os.path.dirname(__file__), "point_cell")
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
MARK = "[cpu-rehearsal] "


def run_benchmark(args, tmp_path, script=RUN, cwd=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one device, like the one-chip machine the driver checks on
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["BENCH_RUN"] = "set by the driver; the benchmark takes no notice"
    return subprocess.run(
        [sys.executable, script, *args, "--out", str(tmp_path / "out")],
        env=env, cwd=cwd or str(tmp_path), capture_output=True, text=True,
        timeout=300,
    )


def rehearse(cell, tmp_path, trace=0, extra=(), seed=2**31 + 11):
    res = run_benchmark(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace), "--cpu-rehearsal", *extra], tmp_path)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    lines = res.stdout.splitlines()
    assert lines and all(ln.startswith(MARK) for ln in lines)
    line = json.loads(lines[-1][len(MARK):])
    notes = json.load(open(
        tmp_path / "out" / f"{cell}.seed{seed}.trace{trace}"
        / "notes.json"))
    return line, notes, lines


def declared(key, cell):
    return {m["name"] for m in MANIFEST[key]
            if cell in m.get("workloads", [cell])}
