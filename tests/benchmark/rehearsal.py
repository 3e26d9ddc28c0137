"""Shared by the tests of the benchmark: the manifests they run over
(`VIEWS`), how a run of benchmark/run.py is started on a machine without a
chip, and how a CPU rehearsal's marked output is read.

No test names a cell, a configuration or a source of the checkout in a list
that a new cell would have to join: what a test runs over comes from a
`View`. There are two: the checkout's BENCHMARK.json, and the checkout's
merged with the fixture `new_config_cell/`, which is what a PR that adds a
configuration and a cell as files and entries would leave behind. A test
that passes over the first and fails over the second has found a wall such
a PR would meet."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmark", "run.py")
# the benchmark's modules import each other as top-level modules
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import run as bench  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
POINT_CELL = os.path.join(HERE, "point_cell")
NEW_CONFIG_CELL = os.path.join(HERE, "new_config_cell")
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
MARK = "[cpu-rehearsal] "
LISTS = ("configs", "workloads", "end_to_end", "per_layer")


class View:
    """A manifest and the roots its files are found under."""

    def __init__(self, name, extra_roots=()):
        self.name = name
        self.extra_roots = list(extra_roots)
        self.catalog = bench.Catalog(self.extra_roots + [REPO])
        self.manifest = {
            **MANIFEST, **{key: self.catalog.merged(key) for key in LISTS}}
        self.cells = [w["name"] for w in self.manifest["workloads"]]
        self.metrics = self.manifest["end_to_end"] + self.manifest["per_layer"]
        self._loaded = {}

    @property
    def root_args(self):
        return [arg for root in self.extra_roots for arg in ("--root", root)]

    def cells_of(self, metric):
        return metric.get("workloads", self.cells)

    def declared(self, key, cell):
        """Names of the metrics of list `key` that the manifest declares
        for the cell."""
        return {m["name"] for m in self.manifest[key]
                if bench.reports(m, cell)}

    def entry(self, key, name):
        return next(e for e in self.manifest[key] if e["name"] == name)

    def config_file(self, config_name):
        """A configuration's file as run, read through its entry's root."""
        root, entry = self.catalog.entry("configs", config_name)
        return json.load(open(os.path.join(root, entry["file"])))

    def layer_files(self):
        return self.catalog.files("layer_metrics", "*.json")

    def cell(self, name):
        """The cell as the harness loads it (`Catalog.cell`), read once."""
        if name not in self._loaded:
            self._loaded[name] = self.catalog.cell(name)
        return self._loaded[name]

    def traffic_of(self, cell):
        return self.cell(cell)["traffic"]

    def kind_of(self, cell):
        return self.cell(cell)["config"]["kind"]


CHECKOUT = View("checkout")
VIEWS = [CHECKOUT, View("with-new-config", [NEW_CONFIG_CELL])]


def over(items, ids=None):
    """Parameters `(view, item)` for every view and every item of it
    (`items(view)` lists them). A case of the checkout's view keeps the
    item's name as its id, as before there were views; a case of another
    view is prefixed with the view's name."""
    ids = ids or (lambda item: item if isinstance(item, str)
                  else item["name"])
    return [
        pytest.param(
            view, item,
            id=ids(item) if view is CHECKOUT else f"{view.name}-{ids(item)}")
        for view in VIEWS for item in items(view)
    ]


def over_cells():
    return over(lambda view: view.cells)


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


def rehearse(cell, tmp_path, trace=0, extra=(), seed=2**31 + 11,
             view=CHECKOUT):
    res = run_benchmark(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace), "--cpu-rehearsal", *view.root_args, *extra],
        tmp_path)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    lines = res.stdout.splitlines()
    assert lines and all(ln.startswith(MARK) for ln in lines)
    line = json.loads(lines[-1][len(MARK):])
    notes = json.load(open(
        tmp_path / "out" / f"{cell}.seed{seed}.trace{trace}"
        / "notes.json"))
    return line, notes, lines
