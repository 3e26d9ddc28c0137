#!/usr/bin/env python3
"""One cell of the benchmark, once, in one process.

  python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Brings the chip up, makes the data from --seed, loads, warms exactly this
cell's shapes, measures for --seconds, checks every answer against the
plain reference AFTER the window, and prints one JSON object as the last
line of stdout: `correct`, `attempted`, `failed`, `metrics`, `device`, and
in a traced run `breakdown`. `--trace 0` reports the cell's end-to-end
metrics, `--trace 1` its per-layer metrics. `setup_s` runs from process
start to the first instant of the window; the check is in neither.
Everything else goes on earlier lines and into `notes.json` under
`benchmark_out/<cell>.seed<n>.trace<t>/`.

The harness is driven by data. A cell is an entry of BENCHMARK.json; its
configuration, its traffic mix and each per-layer metric is a JSON file,
and drivers, metric readers and references are Python files found by name
(see `Catalog`). A new cell of a known kind is new files plus entries.

Without a TPU the run fails and says which platform JAX found.
`--cpu-rehearsal` walks the same control flow on the CPU at a tiny scale
and marks every line of its output, the last included: none is a result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python can tell

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
for _p in (CHECKOUT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: scales of the rehearsal, by configuration kind, and the one option of
#: the program it sets: a row walk this small never costs the 25 ms at
#: which the planner promotes a shape (as in chip_smoke.py)
REHEARSAL_SCALE = {"olap-adopted": 7, "served-store": 6}
REHEARSAL_OPTIONS = {"computer.spillover-min-cost-ms": 0.0}
REHEARSAL_MARK = "[cpu-rehearsal] "


class BenchmarkError(Exception):
    """The run cannot give a result (no chip, a cell that is not there)."""


# ------------------------------------------------------------------ catalog

def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _import_file(path: str):
    name = "benchmark_plugin_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, CHECKOUT)
    )
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def reports(metric_entry: dict, cell: str) -> bool:
    """Whether a cell reports a metric of the manifest: the entry lists the
    cell under `workloads`, or has no such list and is every cell's."""
    return cell in metric_entry.get("workloads", (cell,))


class Catalog:
    """Everything the harness finds by name, under the roots it is given.

    A root is a directory that may hold a `BENCHMARK.json` (whole, or just
    the entries it adds) and, under `benchmark/`, the data and plug-ins:
    `traffic/<mix>.json`, `layer_metrics/<metric>.json`, `drivers/<driver>.py`
    and any `readers/*.py` / `references/*.py`, each of which offers a dict
    `READERS` / `REFERENCES` by name. A configuration's file is the `file`
    of its BENCHMARK.json entry, relative to that root. Earlier roots win
    (`merged` says how entries of one name combine), so a test's root can
    add a cell without touching the checkout's."""

    def __init__(self, roots):
        self.roots = [os.path.abspath(r) for r in roots]
        self.manifests = [
            (root, _read_json(os.path.join(root, "BENCHMARK.json")))
            for root in self.roots
            if os.path.exists(os.path.join(root, "BENCHMARK.json"))
        ]

    def entries(self, key: str) -> list:
        """(root, entry) of every manifest's list `key`, first root first."""
        return [
            (root, entry)
            for root, manifest in self.manifests
            for entry in manifest.get(key, [])
        ]

    def entry(self, key: str, name: str):
        for root, entry in self.entries(key):
            if entry["name"] == name:
                return root, entry
        known = sorted(e["name"] for _, e in self.entries(key))
        raise BenchmarkError(f"no {key} entry named {name!r} (known: {known})")

    def find(self, kind: str, filename: str) -> str:
        for root in self.roots:
            path = os.path.join(root, "benchmark", kind, filename)
            if os.path.exists(path):
                return path
        raise BenchmarkError(
            f"no benchmark/{kind}/{filename} under {self.roots}"
        )

    def files(self, kind: str, pattern: str) -> list:
        """Every `benchmark/<kind>/<pattern>` under the roots, first root
        first, sorted within a root."""
        return [
            path for root in self.roots for path in sorted(glob.glob(
                os.path.join(root, "benchmark", kind, pattern)))
        ]

    def plugins(self, kind: str, table: str) -> dict:
        """The merged `table` dicts of every `benchmark/<kind>/*.py`, an
        earlier root's entry winning."""
        merged = {}
        for path in reversed(self.files(kind, "*.py")):
            merged.update(getattr(_import_file(path), table, {}))
        return merged

    def driver(self, name: str):
        return _import_file(self.find("drivers", name + ".py"))

    def merged(self, key: str) -> list:
        """The manifests' list `key` as one list, one entry per name: the
        first root's entry, and for a metric that several roots declare its
        `workloads` lists joined (no list at all if one of them has none).
        So a root adds a cell to a metric's list by declaring the metric
        again with that cell alone, whatever cells the list has by then."""
        out = {}
        for _, entry in self.entries(key):
            first = out.get(entry["name"])
            if first is None:
                out[entry["name"]] = dict(entry)
            elif "workloads" in first and "workloads" in entry:
                first["workloads"] = list(dict.fromkeys(
                    first["workloads"] + entry["workloads"]))
            else:
                first.pop("workloads", None)
        return list(out.values())

    def cell(self, name: str) -> dict:
        """The cell with all its data: its entry, its configuration, its
        traffic mix, and the metrics it reports. The one rule, for both
        kinds of metric: a cell reports a metric when the manifest's entry
        lists the cell, or has no `workloads` list (`reports`). A layer
        metric is read besides only where its file names the kind of the
        cell's configuration: the file says where its reader finds
        something to read, the manifest says who reports it."""
        _, entry = self.entry("workloads", name)
        root, cfg_entry = self.entry("configs", entry["config"])
        config = _read_json(os.path.join(root, cfg_entry["file"]))
        traffic = _read_json(
            self.find("traffic", entry["traffic"] + ".json")
        )
        declared = {
            key: {e["name"]: e for e in self.merged(key) if reports(e, name)}
            for key in ("end_to_end", "per_layer")
        }
        layer_metrics, seen = [], set()
        for path in self.files("layer_metrics", "*.json"):
            metric = _read_json(path)
            if (
                metric["name"] not in seen
                and config["kind"] in metric["kinds"]
                and metric["name"] in declared["per_layer"]
            ):
                seen.add(metric["name"])
                layer_metrics.append(metric)
        return {
            "name": name,
            "chips": entry["chips"],
            "config": config,
            "traffic": traffic,
            "end_to_end": declared["end_to_end"],
            "layer_metrics": layer_metrics,
        }


# ---------------------------------------------------------------------- run

class Run:
    """What one run knows and records, handed to the driver and readers."""

    def __init__(self, cell, catalog, args, devices, compile_counter):
        self.cell, self.catalog = cell, catalog
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearsal = bool(args.trace), args.cpu_rehearsal
        self.devices = devices
        self.compile_counter = compile_counter
        self.out_dir = os.path.join(
            args.out, f"{cell['name']}.seed{args.seed}.trace{args.trace}"
        )
        self.spans = {}    # name -> seconds on the host clock
        self.counts = {}   # name -> count
        self.notes = {}    # whatever else the notes file should hold
        self.shapes = {}   # vertices, edges: what the bytes functions read
        self.end_to_end = {}  # what the driver measured in the window
        self.registry_before = self.registry_after = None
        self.setup_s = self.window_opened = None
        self.trace_summary = None

    # the deployment, as run: the rehearsal alone changes it
    @property
    def scale(self) -> int:
        if self.rehearsal:
            return REHEARSAL_SCALE[self.config["kind"]]
        return self.config["scale"]

    @property
    def graph_options(self) -> dict:
        return dict(REHEARSAL_OPTIONS) if self.rehearsal else {}

    def say(self, msg: str) -> None:
        mark = REHEARSAL_MARK if self.rehearsal else ""
        print(f"{mark}[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.spans[name] = (
                self.spans.get(name, 0.0) + time.perf_counter() - t
            )

    def reference(self, name: str):
        return self.catalog.plugins("references", "REFERENCES")[name]

    # ---- the window
    def open_window(self) -> float:
        """Set-up ends here; returns the instant on `time.perf_counter`."""
        from janusgraph_tpu.observability import registry

        self.registry_before = registry.snapshot()
        self.compile_counter.phase = "window"
        self.window_opened = time.perf_counter()
        self.setup_s = self.window_opened - _T0
        return self.window_opened

    def close_window(self) -> None:
        from janusgraph_tpu.observability import registry

        self.spans["window"] = time.perf_counter() - self.window_opened
        self.compile_counter.phase = "check"
        self.registry_after = registry.snapshot()

    def moved(self, name: str, field: str = "count") -> float:
        """How far a registry metric's field moved during the window."""
        after = (self.registry_after or {}).get(name, {}).get(field, 0)
        before = (self.registry_before or {}).get(name, {}).get(field, 0)
        return after - before

    # ---- the trace
    def annotate(self, name: str):
        """A span of the benchmark's own in the profiler's trace; nothing
        at all in an untraced run."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        from device import SPAN_PREFIX

        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    @contextlib.contextmanager
    def traced(self):
        """Trace a stretch of the window into `<out>/trace`."""
        import jax

        from device import WINDOW_SPAN

        trace_dir = os.path.join(self.out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        # no per-call Python events: they slow the host they would describe
        # and take stop_trace many seconds to write; the host lines then
        # hold the runtime's own events and the benchmark's annotations
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        t = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
            self.spans["traced"] = time.perf_counter() - t

    def reduce_trace(self) -> None:
        import device
        import trace_reduce

        path = device.newest_xplane(os.path.join(self.out_dir, "trace"))
        if path is None:
            self.say("no trace file was written")
            return
        with self.span("reduce_trace"):
            self.trace_summary = trace_reduce.reduce(device.read_trace(path))
        shutil.rmtree(os.path.join(self.out_dir, "trace"), ignore_errors=True)


def bring_up(cell, rehearsal: bool):
    """The devices of this process, or BenchmarkError without the chips the
    cell asks for. Places the compile cache and starts counting compiles
    before the first jit."""
    import jax

    import device
    try:
        from janusgraph_tpu.olap.device import configure_compile_cache
    except ImportError as e:
        raise BenchmarkError(
            f"the program is not in this checkout ({e}): no result"
        ) from e

    cache_dir = configure_compile_cache()
    counter = device.CompileCounter().register()
    devices = jax.devices()
    found = device.describe(devices)
    want = "cpu" if rehearsal else "tpu"
    if found["platform"] != want:
        raise BenchmarkError(
            f"JAX found platform {found['platform']!r} ({found['kind']!r} x "
            f"{found['count']}), not {want!r}: no result. Only "
            "--cpu-rehearsal runs without a TPU, and its lines are marked."
        )
    if found["count"] < cell["chips"]:
        raise BenchmarkError(
            f"cell {cell['name']} asks for {cell['chips']} chips, JAX found "
            f"{found['count']}: no result"
        )
    if not rehearsal:
        device.peaks(found["kind"])  # an unlisted kind is an error, now
    devices = devices[:cell["chips"]]
    return devices, counter, cache_dir


def guarantees_kept(run: Run) -> bool:
    """Nothing shed, and no spillover fallback of any reason, during the
    window: by construction of the traffic neither can happen, so either
    one moving is a finding, printed, and the run is not correct."""
    shed = run.moved("server.admission.shed")
    fallbacks = {
        name.rsplit(".", 1)[1]: run.moved(name)
        for name in run.registry_after
        if name.startswith("olap.spillover.fallback.") and run.moved(name)
    }
    run.notes["admission_shed"] = shed
    run.notes["spillover_fallbacks"] = fallbacks
    kept = not shed and not fallbacks
    run.say(
        f"{'ok' if kept else 'FINDING'}: server.admission.shed moved by "
        f"{shed}, spillover fallbacks by reason {fallbacks} during the window"
    )
    return kept


def layer_metrics(run: Run) -> dict:
    readers = run.catalog.plugins("readers", "READERS")
    out = {}
    for metric in run.cell["layer_metrics"]:
        value = readers[metric["reader"]](run, **metric.get("args", {}))
        if value is not None:  # a reader that found nothing to read
            out[metric["name"]] = {
                "value": float(value), "unit": metric["unit"],
            }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", action="append", default=[],
                    help="a further directory to find cells and their data "
                         "under, searched before the checkout")
    ap.add_argument("--out", default=os.path.join(CHECKOUT, "benchmark_out"),
                    help="where runs leave their notes (default: "
                         "benchmark_out/ in the checkout)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the control flow on the CPU at a tiny scale; "
                         "every output line is marked, none is a result")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        # before jax is imported: the rehearsal never takes a chip
        os.environ["JAX_PLATFORMS"] = "cpu"

    try:
        catalog = Catalog(args.root + [CHECKOUT])
        cell = catalog.cell(args.workload)
        devices, counter, cache_dir = bring_up(cell, args.cpu_rehearsal)
    except BenchmarkError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 1

    import device

    run = Run(cell, catalog, args, devices, counter)
    os.makedirs(run.out_dir, exist_ok=True)
    found = device.describe(devices)
    if args.cpu_rehearsal:
        run.say("REHEARSAL on the CPU at a tiny scale: checks control flow "
                "only, no line below is a device result")
    run.say(f"{cell['name']} seed={args.seed} seconds={args.seconds} "
            f"trace={args.trace} on {found} compile cache {cache_dir}")

    driver = catalog.driver(run.traffic["driver"])
    state = driver.setup(run)
    try:
        run.open_window()
        end_to_end = run.end_to_end = driver.measure(run, state)
        run.close_window()
        run.say(f"window {run.spans['window']:.2f}s after {run.setup_s:.2f}s "
                f"of set-up; spans {json.dumps(run.spans)}")
        if run.trace:
            run.reduce_trace()
        with run.span("check"):
            verdict = driver.check(run, state)
    finally:
        driver.teardown(run, state)
    run.counts["setup_cache_misses"] = counter.cache_misses.get("setup", 0)
    run.counts["compiles_in_window"] = counter.compiles.get("window", 0)
    correct = guarantees_kept(run) and verdict["failed"] == 0
    run.say(f"failures by reason: {json.dumps(verdict['by_reason'])} of "
            f"{verdict['attempted']} attempted")

    line = {
        "correct": bool(correct),
        "attempted": int(verdict["attempted"]),
        "failed": int(verdict["failed"]),
        "device": {**found, "memory_peak_bytes": device.memory_peak_bytes(
            devices)},
    }
    if run.trace:
        line["metrics"] = layer_metrics(run)
        if run.trace_summary:
            line["device"]["busy_s"] = run.trace_summary["busy_s"]
            line["device"]["window_s"] = run.trace_summary["window_s"]
            line["breakdown"] = {
                key: run.trace_summary[key]
                for key in ("device_ops", "idle_gaps")
            }
    else:
        end_to_end["setup_s"] = run.setup_s
        line["metrics"] = {
            name: {"value": float(value),
                   "unit": cell["end_to_end"][name]["unit"]}
            for name, value in end_to_end.items()
            if name in cell["end_to_end"]
        }
    with open(os.path.join(run.out_dir, "notes.json"), "w") as f:
        json.dump({
            "spans": run.spans, "counts": run.counts, "notes": run.notes,
            "setup_s": run.setup_s, "end_to_end": end_to_end,
            "failures": verdict["by_reason"],
            "trace_summary": run.trace_summary, "line": line,
        }, f, indent=1, default=str)
    print((REHEARSAL_MARK if args.cpu_rehearsal else "") + json.dumps(line),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
