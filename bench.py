#!/usr/bin/env python
"""Benchmark driver: PageRank + 4-hop BFS on graph500-style R-MAT graphs.

Prints ONE JSON line:
  {"metric": "pagerank_edges_per_sec_chip", "value": ..., "unit": "edges/s",
   "vs_baseline": ..., ...extras}

Supervisor/worker split: invoked with no args this script is a SUPERVISOR
that never imports jax itself, so the one process that holds the chip is
the worker.  The actual benchmark (`--worker`) runs in a subprocess and is
STAGED: a backend smoke test first, then per-scale PageRank/BFS runs in
increasing order (s16 -> s20 -> s22 -> s23 by default).  The worker emits
one flushed JSON line per completed stage on stdout plus timestamped
heartbeats on stderr, and the supervisor streams them as they arrive — so
a run that is killed at its budget still leaves every earlier stage's
result recorded.

The final supervisor line reports the LARGEST completed TPU scale, with
per-stage results under "stages".  There is no fallback to another
backend: when the worker finds no TPU the run fails.  A caller who wants
the CPU asks for it with JAX_PLATFORMS=cpu, and the metric is then named
`pagerank_edges_per_sec_cpu`.  The exit code is non-zero when any stage
raised, when the worker died, or when no PageRank stage ran on the
platform asked for.

`vs_baseline`: the reference (JanusGraph FulgoraGraphComputer, a JVM
thread-pool BSP engine) publishes no numbers and cannot run in this
environment (BASELINE.md), so the recorded baseline is a *vectorized
numpy host implementation* of the identical supersteps measured
in-process — a deliberately strong stand-in, making the ratio
conservative.

Env knobs: BENCH_SCALES (default "16,20,22,23" — graph500-s23 north
star last), BENCH_EDGE_FACTOR (16), PR_ITERS (20), BENCH_STRATEGY
(auto|ell|segment|pallas), BENCH_BUDGET_S (supervisor budget, default
2700; the worker is killed when it is spent), BENCH_CPU_SCALE (largest
rung under JAX_PLATFORMS=cpu, 20), BENCH_EXTRAS_SCALE (default 20 — the
ladder rung that additionally runs the CC / peer-pressure / 3-hop-count
headline workloads; must appear in BENCH_SCALES to fire, and its compile
time comes out of BENCH_BUDGET_S before the s23 rung),
BENCH_DENSE_MAX_SCALE (21; dense-BFS comparison rungs above this are
skipped — they cost seconds per hop and the frontier path is the one
users get).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

_REPO_DIR = os.path.dirname(os.path.abspath(__file__))


def _asked_for_cpu() -> bool:
    """The caller pinned JAX to the CPU: the one case in which a run
    without a TPU is a result (under a metric name that says so)."""
    return os.environ.get("JAX_PLATFORMS", "").startswith("cpu")


# --------------------------------------------------------------------------
# supervisor
# --------------------------------------------------------------------------

class _WorkerRun:
    """Run `bench.py --worker`, streaming its per-stage JSON lines."""

    def __init__(self, env: dict):
        self.stages = []
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker"],
            env=env,
            cwd=_REPO_DIR,
            stdout=subprocess.PIPE,
            start_new_session=True,
        )

    def stream(self, deadline: float) -> bool:
        """Read stage lines until EOF or the deadline; True when the worker
        reached EOF by itself (a worker still running at the deadline is
        killed)."""
        done = threading.Event()

        def _reader():
            for raw in self.proc.stdout:
                line = raw.decode("utf-8", "replace").strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if isinstance(obj, dict) and "stage" in obj:
                    self.stages.append(obj)
                    print(f"bench: stage done: {line}", file=sys.stderr)
            done.set()

        t = threading.Thread(target=_reader, daemon=True)
        t.start()
        finished = done.wait(timeout=max(0.0, deadline - time.monotonic()))
        if finished:
            # EOF on stdout: let the interpreter exit so its code is its own
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        else:
            print(
                f"bench: worker deadline reached with "
                f"{len(self.stages)} stages recorded — killing",
                file=sys.stderr,
            )
        self.kill()
        t.join(timeout=30)
        return finished

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                self.proc.kill()
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass


def _final_result(stages, note=None):
    """Merge stage lines into the single output JSON line. Only PageRank
    stages that ran on the platform asked for count: a TPU unless the
    caller pinned the CPU, and then the metric's name says CPU."""
    want = "cpu" if _asked_for_cpu() else "tpu"
    runs = [
        s for s in stages
        if s.get("stage") == "pagerank" and "value" in s
        and s.get("platform") == want
    ]
    best = max(
        runs, key=lambda s: (s.get("scale", 0), s.get("value", 0)),
        default=None,
    )
    out = {
        "metric": "pagerank_edges_per_sec_" + {"tpu": "chip", "cpu": "cpu"}[want],
        "value": 0.0,
        "unit": "edges/s",
        "vs_baseline": 0.0,
        "baseline": "numpy-host-pagerank (proxy; see bench.py docstring)",
    }
    if best is not None:
        for k, v in best.items():
            if k not in ("stage", "metric"):
                out[k] = v
        out["value"] = best["value"]
    smoke = next((s for s in stages if s.get("stage") == "smoke"), None)
    if smoke:
        out["init_s"] = smoke.get("init_s")
        out["smoke_platform"] = smoke.get("platform")
    out["stages"] = [dict(s) for s in stages]
    if best is None:
        out["error"] = f"no pagerank stage completed on platform {want!r}"
    if note:
        out["note"] = note
    return out


def _failed(stages, result) -> bool:
    """A stage raised (its line carries "error"), or nothing ran on the
    platform asked for."""
    return "error" in result or any("error" in s for s in stages)


def supervise() -> int:
    budget = float(os.environ.get("BENCH_BUDGET_S", "2700"))
    deadline = time.monotonic() + budget
    live = {"run": None}

    # if the driver kills us (its own timeout), emit one valid JSON line
    # with everything recorded so far FIRST, then kill the worker group
    def _on_term(_sig, _frm):
        run = live["run"]
        stages = run.stages if run is not None else []
        print(json.dumps(_final_result(
            stages, note="supervisor SIGTERM before completion"
        )))
        sys.stdout.flush()
        if run is not None:
            run.kill()
        os._exit(1)

    signal.signal(signal.SIGTERM, _on_term)
    run = _WorkerRun(dict(os.environ))
    live["run"] = run
    finished = run.stream(deadline)
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    note = None
    if not finished:
        note = f"worker killed at BENCH_BUDGET_S={budget:.0f}"
    elif run.proc.returncode != 0:
        note = f"worker exited with code {run.proc.returncode}"
    result = _final_result(run.stages, note)
    print(json.dumps(result))
    sys.stdout.flush()
    return 1 if (note or _failed(run.stages, result)) else 0


# --------------------------------------------------------------------------
# worker (the actual benchmark; this half imports jax)
# --------------------------------------------------------------------------

#: prior-artifact index for the regression sentinel (built lazily once):
#: BENCH_BASELINE_DIR overrides where prior artifacts are searched;
#: BENCH_REGRESSION=0 disables the compare step entirely
_BASELINE_INDEX = []


def _regression_sentinel(obj: dict) -> None:
    """Attach the `regression` verdict block to one emitted stage: deltas
    vs the best prior artifact for the same (stage, scale, platform,
    host-fallback) cell, or a no-op note when no prior cell matches
    (observability/benchdiff.py — `janusgraph_tpu benchdiff` is the same
    comparison as a CI gate)."""
    if os.environ.get("BENCH_REGRESSION", "1") == "0":
        return
    from janusgraph_tpu.observability.benchdiff import BaselineIndex

    if not _BASELINE_INDEX:
        root = os.path.dirname(os.path.abspath(__file__))
        dirs = [
            d for d in os.environ.get(
                "BENCH_BASELINE_DIR",
                os.pathsep.join(
                    [root, os.path.join(root, "bench_artifacts")]
                ),
            ).split(os.pathsep) if d
        ]
        _BASELINE_INDEX.append(BaselineIndex(dirs))
    _BASELINE_INDEX[0].attach_regression(obj)


def _emit(obj: dict) -> None:
    # every stage line carries the flight-recorder per-category counts at
    # emit time plus the stage's root trace id (stages run under a
    # bench.<stage> span — see _stage_span), so a BENCH_r*.json number
    # correlates straight to the black-box timeline and the span tree
    try:
        from janusgraph_tpu.observability import flight_recorder, tracer

        obj.setdefault("flight_counts", flight_recorder.counts())
        span = tracer.current()
        if span is not None:
            obj.setdefault("trace_id", f"{span.trace_id:016x}")
    except Exception:  # noqa: BLE001 - telemetry must never break the bench
        pass
    try:
        if "stage" in obj:
            # host core count joins the regression cell key: throughput
            # from a 1-core runner is not comparable to an 8-core one
            # (the SATURATE r01->r03 424->360 ops/s "regression")
            obj.setdefault("cpu_count", os.cpu_count())
        if "stage" in obj and "regression" not in obj:
            _regression_sentinel(obj)
    except Exception:  # noqa: BLE001 - the sentinel must never break the bench
        pass
    print(json.dumps(obj))
    sys.stdout.flush()


def _stage_span(name: str, **attrs):
    """Root span for one bench stage; _emit picks its trace_id up."""
    from janusgraph_tpu.observability import tracer

    return tracer.span(f"bench.{name}", **attrs)


def _hb(msg: str, t0: float) -> None:
    print(f"bench worker [{time.monotonic() - t0:8.1f}s] {msg}", file=sys.stderr, flush=True)


def host_pagerank_edges_per_sec(csr, iters: int = 5, damping: float = 0.85) -> float:
    """Vectorized numpy PageRank — the baseline proxy."""
    import numpy as np

    n = csr.num_vertices
    seg = np.repeat(np.arange(n, dtype=np.int64), np.diff(csr.in_indptr))
    src = csr.in_src.astype(np.int64)
    outdeg = np.maximum(csr.out_degree.astype(np.float64), 1.0)
    dangling_mask = csr.out_degree == 0
    rank = np.full(n, 1.0 / n)
    t0 = time.perf_counter()
    for _ in range(iters):
        contrib = rank / outdeg
        agg = np.bincount(seg, weights=contrib[src], minlength=n)
        dangling = rank[dangling_mask].sum()
        rank = (1.0 - damping) / n + damping * (agg + dangling / n)
    dt = time.perf_counter() - t0
    return iters * csr.num_edges / dt


def _bench_scale(
    jax, platform, scale, edge_factor, pr_iters, strategy, t0, extras_scale
):
    """One ladder rung: generate, transfer, compile, run, report."""
    import numpy as np

    from janusgraph_tpu.olap.generators import rmat_csr
    from janusgraph_tpu.olap.programs import PageRankProgram, ShortestPathProgram
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    g0 = time.perf_counter()
    csr = rmat_csr(scale, edge_factor)
    gen_s = time.perf_counter() - g0
    _hb(f"s{scale}: graph ready |V|={csr.num_vertices} |E|={csr.num_edges} "
        f"({gen_s:.1f}s)", t0)

    timed = PageRankProgram(max_iterations=pr_iters, tol=0.0)
    ell_fp = TPUExecutor.ell_footprint(csr)
    _hb(f"s{scale}: ell footprint {ell_fp['bytes']/2**30:.2f}GB "
        f"(pad {ell_fp['pad_ratio']:.2f}x)", t0)
    x0 = time.perf_counter()
    ex = TPUExecutor(csr, strategy=strategy)
    # force device transfer of the aggregation structures now so transfer
    # time is visible separately from compile time
    ex.prewarm(timed)
    transfer_s = time.perf_counter() - x0
    _hb(f"s{scale}: executor built, strategy={ex.strategy} "
        f"(transfer+pack {transfer_s:.1f}s)", t0)

    c0 = time.perf_counter()
    ex.run(timed)  # compile + first run
    compile_s = time.perf_counter() - c0
    _hb(f"s{scale}: pagerank compiled+warm ({compile_s:.1f}s)", t0)

    r0 = time.perf_counter()
    result = ex.run(timed, sync_every=pr_iters)
    jax.block_until_ready(result["rank"])
    pr_s = time.perf_counter() - r0
    pr_eps = pr_iters * csr.num_edges / pr_s
    _hb(f"s{scale}: pagerank {pr_s:.3f}s ({pr_eps:.3e} edges/s)", t0)

    # telemetry snapshot rides the artifact so BENCH_r*.json lines are
    # self-explaining: per-superstep records (wall, frontier, pad,
    # transfer, compile flags — and since PR 5 flops, bytes_accessed,
    # operational_intensity, roofline_utilization, cost_source per
    # superstep) from the registry-published run record. `roofline`
    # carries the device peaks + per-E_cap-tier aggregation the
    # utilization figures are computed against.
    run_rec = dict(ex.last_run_info)
    if run_rec.get("platform") != platform:
        raise RuntimeError(
            f"executor ran on {run_rec.get('platform')!r}, "
            f"the worker holds {platform!r}"
        )
    telemetry = {
        "superstep_records": run_rec.pop("superstep_records", [])[:32],
        "run": {k: v for k, v in run_rec.items() if k != "tiers"},
    }
    roofline = {
        **run_rec.get("roofline", {}),
        "by_tier": run_rec.get("roofline_by_tier", {}),
    }
    steps = telemetry["superstep_records"]
    if steps:
        utils = [
            r["roofline_utilization"] for r in steps
            if r.get("roofline_utilization") is not None
        ]
        roofline["operational_intensity"] = steps[-1].get(
            "operational_intensity"
        )
        roofline["utilization_mean"] = (
            round(sum(utils) / len(utils), 6) if utils else None
        )
        roofline["cost_source"] = steps[-1].get("cost_source")

    # ISSUE 6: the tuner's decision block + a pure-ELL vs hybrid A/B in
    # the SAME round on the SAME graph — the measured proof behind the
    # decision (pad ratio + superstep wall per layout). The headline run
    # above already measured whatever the tuner picked; only the missing
    # side(s) pay an extra compile+run here.
    autotune_rec = run_rec.get("autotune")
    ab = {}
    if os.environ.get("BENCH_AB", "1") != "0":
        resolved = run_rec.get("strategy_resolved")
        measured = {
            resolved: (1000.0 * pr_s / pr_iters, run_rec.get("pad_ratio")),
        }
        for strat in ("ell", "hybrid"):
            if strat in measured:
                continue
            ex_b = TPUExecutor(csr, strategy=strat)
            ex_b.run(timed)  # compile + warm (persistent cache amortizes)
            b0 = time.perf_counter()
            out_b = ex_b.run(timed, sync_every=pr_iters)
            jax.block_until_ready(out_b["rank"])
            b_s = time.perf_counter() - b0
            measured[strat] = (
                1000.0 * b_s / pr_iters,
                ex_b.last_run_info.get("pad_ratio"),
            )
            _hb(f"s{scale}: A/B {strat} {b_s:.3f}s "
                f"(pad {measured[strat][1]})", t0)
            del ex_b, out_b
        if "ell" in measured and "hybrid" in measured:
            ell_ms, ell_pad = measured["ell"]
            hyb_ms, hyb_pad = measured["hybrid"]
            ab = {
                "ell_superstep_ms": round(ell_ms, 3),
                "hybrid_superstep_ms": round(hyb_ms, 3),
                "ell_pad_ratio": ell_pad,
                "hybrid_pad_ratio": hyb_pad,
                "hybrid_speedup": round(ell_ms / max(hyb_ms, 1e-9), 3),
                "headline_strategy": resolved,
            }

    base_iters = 3 if scale >= 20 else 5
    base_eps = host_pagerank_edges_per_sec(csr, iters=base_iters)

    # Fulgora-analogue architecture baseline (VERDICT r3 #5): the
    # reference's threaded per-vertex hash-map BSP, measured — only at
    # modest scales (pure-python per-edge cost; s20 = ~4.3s/superstep)
    fulgora_fields = {}
    if scale <= 20 and os.environ.get("BENCH_FULGORA", "1") != "0":
        from janusgraph_tpu.olap.fulgora_baseline import (
            measure_fulgora_baseline,
        )

        fb = measure_fulgora_baseline(
            csr, iterations=3 if scale <= 16 else 1
        )
        fulgora_fields = {
            "fulgora_analogue_eps": round(fb["edges_per_sec"], 1),
            "vs_fulgora_analogue": round(pr_eps / fb["edges_per_sec"], 1),
            "fulgora_note": "python analogue of "
                "FulgoraGraphComputer.java:210-230 (GIL-bound; "
                "see olap/fulgora_baseline.py)",
        }
        _hb(f"s{scale}: fulgora-analogue {fb['edges_per_sec']:.3e} edges/s "
            f"(tpu/cpu path is {pr_eps / fb['edges_per_sec']:.0f}x)", t0)

    # the pagerank stage emits BEFORE the BFS section, so a run killed at
    # its budget later in the rung keeps the rung's headline measurement
    _emit({
        "stage": "pagerank",
        "value": round(pr_eps, 1),
        "vs_baseline": round(pr_eps / base_eps, 3),
        **fulgora_fields,
        "platform": platform,
        "device_kind": run_rec.get("device_kind"),
        "device_count": run_rec.get("device_count"),
        "strategy": ex.strategy,
        "scale": scale,
        "edge_factor": edge_factor,
        "num_vertices": csr.num_vertices,
        "num_edges": csr.num_edges,
        "pr_iters": pr_iters,
        "pagerank_wall_s": round(pr_s, 3),
        "pagerank_superstep_ms": round(1000.0 * pr_s / pr_iters, 3),
        "graph_gen_s": round(gen_s, 2),
        "transfer_pack_s": round(transfer_s, 2),
        "compile_s": round(compile_s, 2),
        # one-time setup vs steady state: compiles persist in the
        # compile cache, generation and transfer are paid once per
        # executor lifetime — steady-state cost is the run walls
        "setup_once_s": round(gen_s + transfer_s + compile_s, 2),
        "setup_amortization": "compile cached across runs; "
                              "gen + transfer once per executor",
        "ell_bytes": ell_fp["bytes"],
        "ell_pad_ratio": round(ell_fp["pad_ratio"], 3),
        # run-resolved layout's pad (the ell_pad_ratio above is the pure-
        # ELL footprint estimate the rounds have always tracked)
        "pad_ratio": run_rec.get("pad_ratio"),
        "strategy_resolved": run_rec.get("strategy_resolved"),
        "autotune": autotune_rec,
        "ab": ab,
        "roofline": roofline,
        "telemetry": telemetry,
    })

    # BFS both ways: frontier-compacted (the default; olap/frontier.py) and
    # the dense BSP path it replaces — the delta is the VERDICT r3 #1 claim.
    # Seed at the max-out-degree hub: seed 0 can be a SINK on R-MAT draws
    # (observed at s20: out-degree 0 -> a one-hop no-op "benchmark"), and
    # hub-seeded 4-hop reaches most of the graph — the honest workload.
    bfs_seed = int(np.argmax(csr.out_degree))
    bfs_prog = ShortestPathProgram(seed_index=bfs_seed, max_iterations=4)
    ex.run(bfs_prog)  # warm: compiles the per-tier step executables
    b0 = time.perf_counter()
    bfs_res = ex.run(bfs_prog)
    jax.block_until_ready(bfs_res["distance"])
    bfs_s = time.perf_counter() - b0
    _hb(f"s{scale}: bfs-4hop frontier {bfs_s:.3f}s", t0)
    _emit({
        "stage": "bfs",
        "platform": platform,
        "scale": scale,
        "bfs_4hop_wall_s": round(bfs_s, 3),
        "bfs_strategy": ex.last_run_info.get("path", "unknown"),
        "bfs_seed": bfs_seed,
        "bfs_frontier_tiers": [
            {k: t[k] for k in ("hop", "frontier", "edges", "E_cap")}
            for t in ex.last_run_info.get("tiers", [])
        ],
    })

    # dense comparison capped by default: the dense executables at the top
    # rungs are exactly the gather-wall walls the r3 artifacts measured
    # (s23 dense 4-hop 7.6-8.3s) — keep the ladder's critical path off them
    dense_max = int(os.environ.get("BENCH_DENSE_MAX_SCALE", "21"))
    if scale <= dense_max:
        ex.run(bfs_prog, frontier="off")
        b0 = time.perf_counter()
        bfs_dense = ex.run(bfs_prog, sync_every=4, frontier="off")
        jax.block_until_ready(bfs_dense["distance"])
        bfs_dense_s = time.perf_counter() - b0
        _hb(f"s{scale}: bfs-4hop dense {bfs_dense_s:.3f}s "
            f"(frontier speedup {bfs_dense_s / max(bfs_s, 1e-9):.1f}x)", t0)
        _emit({
            "stage": "bfs_dense",
            "platform": platform,
            "scale": scale,
            "bfs_dense_4hop_wall_s": round(bfs_dense_s, 3),
            "bfs_frontier_speedup": round(
                bfs_dense_s / max(bfs_s, 1e-9), 2
            ),
        })

    # Remaining BASELINE.md headline workloads (configs #2/#4/#5) at ONE
    # ladder scale: ConnectedComponent, PeerPressure label propagation
    # (phase-alternating -> host-loop path), and the 3-hop
    # TraversalVertexProgram-analogue count. Gated so the budget cost is
    # bounded; compile cache amortizes re-runs.
    # Under JAX_PLATFORMS=cpu the extras run at the CHEAP rung (s16)
    # instead of being skipped, so all five BASELINE workload shapes still
    # run (VERDICT r4 weak #5) — the s20 peer-pressure compile alone runs
    # minutes on host XLA (measured round 4), but s16 fits. The rung is
    # chosen (and clamped) once in worker() and passed in.
    if scale == extras_scale:
        from janusgraph_tpu.olap.programs import (
            ConnectedComponentsProgram,
            PeerPressureProgram,
            TraversalCountProgram,
        )

        def _workload(name, prog, result_key=None, post=None, **runkw):
            ex.run(prog, **runkw)  # compile + warm the SAME configuration
            r0 = time.perf_counter()
            res = ex.run(prog, **runkw)
            if result_key is not None:
                np.asarray(res[result_key])  # ensure fetched before stopping
            wall = round(time.perf_counter() - r0, 3)
            line = {
                "stage": "workload", "workload": name,
                "platform": platform, "scale": scale, "wall_s": wall,
            }
            if post is not None:
                line.update(post(res))
            _hb(f"s{scale}: {name} {wall}s", t0)
            _emit(line)  # one line per workload: a later hang loses nothing

        # min-label propagation converges within the component diameter;
        # 64 covers R-MAT's O(log n) diameter with a wide margin at any
        # ladder scale, and terminate_device stops the loop at fixpoint
        _workload(
            "connected_components",
            ConnectedComponentsProgram(max_iterations=64),
            result_key="component",
            post=lambda res: {
                "components": int(len(np.unique(np.asarray(res["component"])))),
                "iter_cap": 64,
            },
        )
        # phase-alternating combiner -> host-loop path; sync_every matters
        _workload(
            "peer_pressure",
            PeerPressureProgram(rounds=5),
            result_key="cluster",
            sync_every=5,
        )
        _workload(
            "traversal_3hop_count",
            TraversalCountProgram(hops=3),
            result_key="count",
            post=lambda res: {"paths": float(np.asarray(res["count"]).sum())},
        )
        # filtered 3-hop: mid-chain has()-filter via device mask (the
        # TraversalVertexProgram-with-HasStep shape; VERDICT r3 #4)
        from janusgraph_tpu.olap.programs.olap_traversal import (
            OLAPTraversalProgram,
            PropertyFilter,
            TraversalStep,
            evaluate_filter_mask,
        )
        from janusgraph_tpu.core.predicates import Cmp

        prop_rng = np.random.default_rng(scale)
        csr.properties["score"] = prop_rng.uniform(
            0, 10, csr.num_vertices
        ).astype(np.float32)
        flt = (PropertyFilter("score", Cmp.GREATER_THAN, 5.0),)
        fmask = evaluate_filter_mask(csr, flt)
        steps_f = (
            TraversalStep("out"),
            TraversalStep("out", None, flt),
            TraversalStep("out"),
        )
        masks = np.stack(
            [np.ones(csr.num_vertices, np.float32), fmask,
             np.ones(csr.num_vertices, np.float32)], axis=1,
        )
        _workload(
            "filtered_3hop",
            OLAPTraversalProgram(steps_f, step_masks=masks),
            result_key="count",
            post=lambda res: {
                "paths": float(np.asarray(res["count"]).sum()),
                "filter_selectivity": round(float(fmask.mean()), 3),
            },
        )
        # path()-carrying OLAP traversal (VERDICT r4 #4): device reach
        # masks + host backward enumeration, seeded (full-V 3-hop path
        # enumeration is combinatorial; the count sum prices it)
        from janusgraph_tpu.olap.programs.olap_traversal import (
            enumerate_paths,
        )

        rng_p = np.random.default_rng(7)
        pseeds = tuple(
            int(s) for s in rng_p.choice(csr.num_vertices, 8, replace=False)
        )
        prog_p = OLAPTraversalProgram(
            (TraversalStep("out"), TraversalStep("out"),
             TraversalStep("out")),
            seed_indices=pseeds, record_reach=True,
        )
        ex.run(prog_p)
        r0 = time.perf_counter()
        res_p = ex.run(prog_p)
        device_wall = round(time.perf_counter() - r0, 3)
        r0 = time.perf_counter()
        sample = list(enumerate_paths(csr, prog_p, res_p, limit=10_000))
        enum_wall = round(time.perf_counter() - r0, 3)
        _hb(f"s{scale}: paths_3hop device {device_wall}s "
            f"enum[{len(sample)}] {enum_wall}s", t0)
        _emit({
            "stage": "workload", "workload": "paths_3hop_seeded",
            "platform": platform, "scale": scale,
            "wall_s": device_wall, "enum_wall_s": enum_wall,
            "seeds": len(pseeds), "paths_enumerated": len(sample),
            # f64 accumulator; per-vertex f32 counts cap exactness at 2^24
            # per vertex — beyond that the total is an estimate
            "paths_total": float(
                np.asarray(res_p["count"], np.float64).sum()
            ),
        })

        # LDBC-SNB-shaped proxy (BASELINE configs #2/#5 datasets): CC +
        # filtered 3-hop on a community-structured heavy-tail graph, one
        # scale below the R-MAT rung (same |E| order)
        from janusgraph_tpu.olap.generators import ldbc_snb_csr

        lcsr = ldbc_snb_csr(scale)
        _hb(f"s{scale}: ldbc-shaped proxy |V|={lcsr.num_vertices} "
            f"|E|={lcsr.num_edges}", t0)
        lex = TPUExecutor(lcsr, strategy=strategy)

        def _lworkload(name, prog, result_key, post=None, **runkw):
            lex.run(prog, **runkw)
            r0 = time.perf_counter()
            res = lex.run(prog, **runkw)
            np.asarray(res[result_key])
            wall = round(time.perf_counter() - r0, 3)
            line = {
                "stage": "workload", "workload": name, "dataset": "ldbc-shaped",
                "platform": platform, "scale": scale, "wall_s": wall,
                "num_edges": lcsr.num_edges,
            }
            if post is not None:
                line.update(post(res))
            _hb(f"s{scale}: {name} {wall}s", t0)
            _emit(line)

        _lworkload(
            "connected_components_ldbc",
            ConnectedComponentsProgram(max_iterations=64),
            "component",
            post=lambda res: {
                "components": int(
                    len(np.unique(np.asarray(res["component"])))
                ),
            },
        )
        lmask = evaluate_filter_mask(
            lcsr, (PropertyFilter("creation_day", Cmp.GREATER_THAN, 1825),)
        )
        _lworkload(
            "filtered_3hop_ldbc",
            OLAPTraversalProgram(
                (
                    TraversalStep("out"),
                    TraversalStep(
                        "out", None,
                        (PropertyFilter("creation_day", Cmp.GREATER_THAN,
                                        1825),),
                    ),
                    TraversalStep("out"),
                ),
                step_masks=np.stack(
                    [np.ones(lcsr.num_vertices, np.float32), lmask,
                     np.ones(lcsr.num_vertices, np.float32)], axis=1,
                ),
            ),
            "count",
            post=lambda res: {"paths": float(np.asarray(res["count"]).sum())},
        )
        del lex, lcsr

    # dense-feature tier stage (ISSUE 7, optional: BENCH_DENSE=1): the
    # 2-layer GCN forward — a fused gather->aggregate->matmul superstep —
    # at the extras rung, with the per-superstep MXU accounting and a
    # same-round ELL vs hybrid A/B so the artifact carries both layouts'
    # measured pad + wall for the [n, d] message class
    if scale == extras_scale and os.environ.get("BENCH_DENSE", "0") == "1":
        from janusgraph_tpu.olap.programs import GCNForwardProgram

        d_dim = int(os.environ.get("BENCH_DENSE_DIM", "32"))
        layers = int(os.environ.get("BENCH_DENSE_LAYERS", "2"))
        mk = lambda: GCNForwardProgram(  # noqa: E731
            feature_dim=d_dim, hidden_dim=d_dim, out_dim=d_dim,
            num_layers=layers,
        )
        dense_ab = {}
        dense_mxu = {}
        dense_steps = []
        for strat in ("ell", "hybrid"):
            ex_d = TPUExecutor(csr, strategy=strat)
            ex_d.run(mk())  # compile + warm
            d0 = time.perf_counter()
            out_d = ex_d.run(mk(), sync_every=layers)
            jax.block_until_ready(out_d["h"])
            d_s = time.perf_counter() - d0
            inf = ex_d.last_run_info
            dense_ab[strat] = {
                "superstep_ms": round(1000.0 * d_s / layers, 3),
                "pad_ratio": inf.get("pad_ratio"),
                "mxu_utilization_mean": (
                    (inf.get("mxu") or {}).get("mean_utilization")
                ),
            }
            if strat == "hybrid":
                dense_mxu = inf.get("mxu") or {}
                dense_steps = [
                    {
                        k: r.get(k)
                        for k in ("step", "wall_ms", "mxu_flops",
                                  "mxu_utilization",
                                  "roofline_utilization")
                    }
                    for r in inf.get("superstep_records", [])[:16]
                ]
            _hb(f"s{scale}: dense-gcn {strat} {d_s:.3f}s "
                f"(pad {dense_ab[strat]['pad_ratio']})", t0)
            del ex_d, out_d
        e_ms = dense_ab["ell"]["superstep_ms"]
        h_ms = dense_ab["hybrid"]["superstep_ms"]
        _emit({
            "stage": "dense_gcn",
            "platform": platform,
            "scale": scale,
            "feature_dim": d_dim,
            "num_layers": layers,
            "gcn_superstep_ms": h_ms,
            "mxu": dense_mxu,
            "superstep_records": dense_steps,
            "ab": {
                "ell": dense_ab["ell"],
                "hybrid": dense_ab["hybrid"],
                "hybrid_speedup": round(e_ms / max(h_ms, 1e-9), 3),
            },
        })
    del ex, csr


def _run_stage(failed: list, t0, name: str, fn, *args, **fields) -> bool:
    """Run one stage under its `bench.<name>` span (`fields` annotate the
    span, and the error line if there is one). A stage that raises is
    reported (heartbeat + a stage line carrying "error") and recorded in
    `failed`, which makes the worker's exit code non-zero; later stages
    still run, so one failure does not hide what else works."""
    try:
        with _stage_span(name, **fields):
            fn(*args)
        return True
    except Exception as e:  # noqa: BLE001 - reported, then fails the run
        _hb(f"{name} stage FAILED {type(e).__name__}: {e}", t0)
        _emit({
            "stage": name, "ok": False, **fields,
            "error": f"{type(e).__name__}: {e}"[:500],
        })
        failed.append(name)
        return False


def worker() -> int:
    t0 = time.monotonic()
    _hb("interpreter up", t0)

    import jax

    from janusgraph_tpu.olap.device import configure_compile_cache

    # persistent compilation cache: the bucket-aggregate executables are
    # compile-heavy (~1min at s20+), and a re-run of the same ladder
    # should pay that once per shape
    _hb(f"compile cache: {configure_compile_cache()}", t0)

    i0 = time.perf_counter()
    devs = jax.devices()
    init_s = time.perf_counter() - i0
    platform = devs[0].platform
    _hb(f"backend up: platform={platform} kind={devs[0].device_kind} "
        f"devices={len(devs)} ({init_s:.1f}s)", t0)
    if platform != "tpu" and not _asked_for_cpu():
        _emit({
            "stage": "smoke", "ok": False, "platform": platform,
            "error": f"no TPU: JAX found platform {platform!r} "
                     "(set JAX_PLATFORMS=cpu to ask for a CPU run)",
        })
        return 1

    # smoke: one tiny matmul proves the data path end to end
    import jax.numpy as jnp

    s0 = time.perf_counter()
    x = jnp.ones((512, 512), dtype=jnp.bfloat16)
    y = float(jnp.float32((x @ x).sum()))
    smoke_s = time.perf_counter() - s0
    _hb(f"smoke matmul ok ({smoke_s:.1f}s, sum={y:.0f})", t0)
    _emit({
        "stage": "smoke",
        "platform": platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "init_s": round(init_s, 1),
        "matmul_s": round(smoke_s, 1),
    })

    if os.environ.get("BENCH_SCALES"):
        scales = [int(s) for s in os.environ["BENCH_SCALES"].split(",")]
    elif os.environ.get("BENCH_SCALE"):  # single-scale back-compat (cli.py)
        scales = [int(os.environ["BENCH_SCALE"])]
    else:
        scales = [16, 20, 22, 23]
    # the one rung where the BASELINE workload extras fire (computed HERE,
    # passed down — the worker's clamping and _bench_scale's gate must
    # agree or the extras silently never run)
    extras_env = os.environ.get("BENCH_EXTRAS_SCALE")
    if platform == "cpu":
        # clamp the ladder to the CPU cap: the cheap extras rung (s16,
        # where the five BASELINE workload shapes run — see _bench_scale)
        # plus the largest affordable pagerank rung
        cap = int(os.environ.get("BENCH_CPU_SCALE", "20"))
        extras_scale = min(int(extras_env) if extras_env else 16, cap)
        scales = sorted({extras_scale, min(max(scales), cap)})
    else:
        extras_scale = int(extras_env) if extras_env else 20
    edge_factor = int(os.environ.get("BENCH_EDGE_FACTOR", "16"))
    pr_iters = int(os.environ.get("PR_ITERS", "20"))
    strategy = os.environ.get("BENCH_STRATEGY", "auto")

    failed: list = []

    def on(var: str) -> bool:
        return os.environ.get(var, "0") == "1"

    for scale in scales:
        if not _run_stage(
            failed, t0, "rung", _bench_scale, jax, platform, scale,
            edge_factor, pr_iters, strategy, t0, extras_scale,
            scale=scale, platform=platform,
        ):
            break  # a rung that fails stops the climb

    # BASELINE dataset-fidelity rows (configs #2/#4)
    if os.environ.get("BENCH_DATASETS", "1") != "0":
        _run_stage(failed, t0, "dataset", _datasets_stage, jax, platform, t0)
    # OLTP micro-bench: host-side, platform-independent, bounded by the
    # edge cap (~10-20s for both backends)
    if os.environ.get("BENCH_OLTP", "1") != "0":
        _run_stage(failed, t0, "oltp", _oltp_stage, t0)
    # pipelined wire-protocol A/B (ISSUE 11): remote multiquery
    # throughput, synchronous vs pipelined framing, with a depth sweep
    if os.environ.get("BENCH_PIPELINE", "1") != "0":
        _run_stage(failed, t0, "oltp_pipeline", _oltp_pipeline_stage, t0)
    # The stages below are off unless asked for; each one's docstring says
    # what it certifies and which artifact it writes.
    if on("BENCH_SPILLOVER"):
        _run_stage(failed, t0, "oltp_spillover", _oltp_spillover_stage, t0)
    if on("BENCH_STREAM"):
        _run_stage(
            failed, t0, "streaming_freshness", _streaming_freshness_stage, t0
        )
    if on("BENCH_CHAOS"):
        _run_stage(failed, t0, "chaos", _chaos_stage, t0)
    if on("MULTICHIP_CHAOS"):
        _run_stage(failed, t0, "multichip_chaos", _multichip_chaos_stage, t0)
    if on("MULTICHIP"):
        _run_stage(failed, t0, "multichip_ab", _multichip_ab_stage, t0)
    if on("SATURATE"):
        _run_stage(failed, t0, "saturate", _saturate_stage, t0)
    fleet = on("FLEET")
    if fleet:
        _run_stage(failed, t0, "fleet_chaos", _fleet_chaos_stage, t0)
    if fleet or on("FLEET_CDC"):
        _run_stage(
            failed, t0, "fleet_cdc_failover", _fleet_cdc_failover_stage, t0
        )
    if fleet or on("FLEET_STALL"):
        _run_stage(
            failed, t0, "fleet_stall_forensics", _stall_forensics_stage, t0
        )
    if fleet or on("FLEET_PUSH"):
        _run_stage(failed, t0, "fleet_push_poll", _fleet_push_stage, t0)
    # the Pallas kernel compiled by Mosaic at s16, with parity vs the ELL
    # result (only a TPU compiles it; elsewhere it is interpreted)
    if platform == "tpu" and os.environ.get("BENCH_PALLAS", "1") != "0":
        _run_stage(failed, t0, "pallas", _pallas_stage, jax, pr_iters, t0)
    if failed:
        _hb(f"failed stages: {', '.join(failed)}", t0)
    return 1 if failed else 0


def _chaos_stage(t0):
    """Seeded chaos soak (storage/faults.py): N transactions through
    injected temporary faults + one torn batch, crash, reopen with
    torn-commit recovery, and finish. Emits recovered-op counts (retries
    absorbed below the workload) and recovery latency so robustness cost
    is a tracked number, not folklore."""
    from janusgraph_tpu.core.graph import JanusGraphTPU
    from janusgraph_tpu.exceptions import (
        InjectedCrashError,
        TemporaryBackendError,
    )
    from janusgraph_tpu.observability import registry
    from janusgraph_tpu.storage.inmemory import InMemoryStoreManager

    n_txs = int(os.environ.get("BENCH_CHAOS_TXS", "300"))
    seed = int(os.environ.get("BENCH_CHAOS_SEED", "42"))
    base = {
        "ids.authority-wait-ms": 0.0,
        "locks.wait-ms": 0.0,
        "tx.log-tx": True,
        "tx.max-commit-time-ms": 0.0,
        "storage.backoff-base-ms": 1.0,
        "storage.backoff-max-ms": 4.0,
    }
    chaos = {
        **base,
        "storage.faults.enabled": True,
        "storage.faults.seed": seed,
        "storage.faults.read-error-rate": 0.02,
        "storage.faults.write-error-rate": 0.02,
        "storage.faults.torn-mutation-at": n_txs // 2,
        "storage.faults.lock-expiry-at": n_txs // 3,
    }
    retries_before = registry.get_count("storage.backend_op.retries")
    mgr = InMemoryStoreManager()
    w0 = time.perf_counter()
    graph = JanusGraphTPU(chaos, store_manager=mgr)
    plan = graph.fault_plan
    mgmt = graph.management()
    mgmt.make_property_key("uid", int)
    mgmt.build_composite_index("chaosByUid", ["uid"], unique=True)

    def write(g, i):
        retries = 12
        for attempt in range(retries):
            tx = g.new_transaction()
            try:
                tx.add_vertex(uid=i)
                tx.commit()
                return
            except TemporaryBackendError:
                if tx.is_open:
                    tx.rollback()
                if attempt == retries - 1:
                    raise

    crashed_at = None
    for i in range(n_txs):
        try:
            write(graph, i)
        except InjectedCrashError:
            crashed_at = i
            break
    r0 = time.perf_counter()
    graph2 = JanusGraphTPU(base, store_manager=mgr)  # recovery runs here
    recovery_ms = (time.perf_counter() - r0) * 1000.0
    for i in range((crashed_at + 1) if crashed_at is not None else n_txs,
                   n_txs):
        write(graph2, i)
    txc = graph2.new_transaction(read_only=True)
    present = sum(
        1 for i in range(n_txs)
        if graph2.index_lookup(txc, "chaosByUid", (i,))
    )
    txc.rollback()
    injected = {}
    for e in plan.journal:
        injected[e["kind"]] = injected.get(e["kind"], 0) + 1
    rec = graph2.last_torn_recovery or {}
    _emit({
        "stage": "chaos",
        "ok": present == n_txs,
        "seed": seed,
        "txs": n_txs,
        "crashed_at": crashed_at,
        "vertices_present": present,
        "injected": injected,
        "recovered_ops": registry.get_count("storage.backend_op.retries")
        - retries_before,
        "torn_replayed": len(rec.get("replayed", ())),
        "torn_rolled_back": len(rec.get("rolled_back", ())),
        "recovery_open_ms": round(recovery_ms, 2),
        "wall_s": round(time.perf_counter() - w0, 3),
        **_chaos_flight_dump(),
    })
    graph2.close()
    _hb(f"chaos stage ok ({present}/{n_txs} present)", t0)


def _multichip_chaos_stage(t0):
    """8-virtual-device chaos soak via the hermetic dryrun subprocess
    (__graft_entry__._chaos_multichip_inproc): injected shard preemption,
    collective timeout, straggler skew, and a torn manifest write, all
    absorbed by sharded-checkpoint auto-resume with bitwise-identical
    final state on {sharded x ell/segment, cpu x ell/hybrid}. The
    subprocess re-execs with the forced CPU mesh, so this stage is safe
    to run from a TPU-configured bench process."""
    import json
    import subprocess
    import sys
    import tempfile

    n_dev = int(os.environ.get("MULTICHIP_CHAOS_DEVICES", "8"))
    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as d:
        out_path = os.path.join(d, "multichip_chaos.json")
        env = dict(os.environ)
        env["MULTICHIP_CHAOS"] = "1"
        env["MULTICHIP_OUT"] = out_path
        w0 = time.perf_counter()
        res = subprocess.run(
            [sys.executable, "-c",
             f"import __graft_entry__ as ge; ge.dryrun_multichip({n_dev})"],
            cwd=repo, env=env, capture_output=True, text=True,
            timeout=float(os.environ.get("MULTICHIP_CHAOS_TIMEOUT_S", "600")),
        )
        wall_s = time.perf_counter() - w0
        if res.returncode != 0 or not os.path.exists(out_path):
            _emit({
                "stage": "multichip_chaos", "ok": False,
                "rc": res.returncode,
                "error": (res.stderr or "")[-500:],
            })
            _hb(f"multichip_chaos FAILED rc={res.returncode}", t0)
            return
        with open(out_path) as f:
            chaos = json.load(f)
    _emit({
        "stage": "multichip_chaos",
        "ok": True,
        "wall_s": round(wall_s, 3),
        **chaos,
    })
    _hb(
        f"multichip_chaos ok (recovered_supersteps="
        f"{chaos['recovered_supersteps']}, skew={chaos['shard_skew']})",
        t0,
    )


def _multichip_ab_stage(t0):
    """Eager-vs-blocked exchange A/B on the 8-virtual-device mesh via the
    hermetic dryrun subprocess (__graft_entry__._ab_multichip_inproc):
    per-cell superstep_ms + exchange elems/bytes/batches for
    {a2a-ell, a2a-segment, ring-segment, blocked-ell, blocked-segment}
    scalar PageRank cells, dense-feature GCN cells on the fan-in graph
    when BENCH_DENSE=1, blocked cells certified bitwise against
    halo.replay_superstep, BFS bitwise blocked-vs-eager."""
    import json
    import subprocess
    import sys
    import tempfile

    n_dev = int(os.environ.get("MULTICHIP_DEVICES", "8"))
    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as d:
        out_path = os.path.join(d, "multichip_ab.json")
        env = dict(os.environ)
        env["MULTICHIP_OUT"] = out_path
        env.setdefault("BENCH_DENSE", "1")
        w0 = time.perf_counter()
        res = subprocess.run(
            [sys.executable, "-c",
             "import __graft_entry__ as ge; "
             f"ge.dryrun_multichip_ab({n_dev})"],
            cwd=repo, env=env, capture_output=True, text=True,
            timeout=float(os.environ.get("MULTICHIP_AB_TIMEOUT_S", "900")),
        )
        wall_s = time.perf_counter() - w0
        if res.returncode != 0 or not os.path.exists(out_path):
            _emit({
                "stage": "multichip_ab", "ok": False,
                "rc": res.returncode,
                "error": (res.stderr or "")[-500:],
            })
            _hb(f"multichip_ab FAILED rc={res.returncode}", t0)
            return
        with open(out_path) as f:
            ab = json.load(f)
    _emit({
        "stage": "multichip_ab",
        "ok": True,
        "wall_s": round(wall_s, 3),
        **ab,
    })
    hd = ab.get("headline", {})
    _hb(
        "multichip_ab ok (dense blocked-vs-eager "
        f"{hd.get('dense_speedup_blocked_vs_eager')}x, "
        f"batches {hd.get('batches_blocked')} vs ring "
        f"{hd.get('batches_ring_eager')})",
        t0,
    )


def _chaos_flight_dump() -> dict:
    """BENCH_CHAOS extra: write a flight-recorder dump of the chaos run
    and record its size + write latency, so the artifact tracks the cost
    of the black box itself over rounds."""
    from janusgraph_tpu.observability import flight_recorder

    d0 = time.perf_counter()
    path = flight_recorder.dump(reason="bench-chaos")
    dump_ms = (time.perf_counter() - d0) * 1000.0
    if path is None:
        return {"flight_dump": None}
    return {
        "flight_dump": path,
        "flight_dump_bytes": os.path.getsize(path),
        "flight_dump_ms": round(dump_ms, 3),
        "flight_dump_events": flight_recorder.occupancy,
    }


def _datasets_stage(jax, platform, t0):
    """BASELINE dataset-fidelity rows (VERDICT r4 #6): ConnectedComponents
    on the LDBC-SF1-SIZED SNB-shaped proxy (config #2) and PeerPressure on
    the Twitter-2010-shaped power-law proxy (config #4). On TPU the LDBC
    proxy is the documented SF1 size (3.2M vertices / 17.3M edges) and the
    Twitter proxy runs 2M vertices / 73M edges; a CPU run (asked for with
    JAX_PLATFORMS=cpu) runs the same SHAPES scaled down."""
    import numpy as np

    from janusgraph_tpu.olap.generators import ldbc_sf_csr, twitter_csr
    from janusgraph_tpu.olap.programs import (
        ConnectedComponentsProgram,
        PeerPressureProgram,
    )
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    if platform == "tpu":
        ldbc_kw = {"sf": 1, "scale_down": 1}
        tw_n, tw_ef = 1 << 21, 35.0
    else:
        ldbc_kw = {"sf": 1, "scale_down": 8}
        tw_n, tw_ef = 1 << 16, 35.0

    g0 = time.perf_counter()
    lcsr = ldbc_sf_csr(**ldbc_kw)
    _hb(f"datasets: ldbc-sf1 proxy |V|={lcsr.num_vertices} "
        f"|E|={lcsr.num_edges} ({time.perf_counter() - g0:.1f}s)", t0)
    ex = TPUExecutor(lcsr)
    prog = ConnectedComponentsProgram(max_iterations=64)
    ex.run(prog)
    r0 = time.perf_counter()
    res = ex.run(prog)
    comp = np.asarray(res["component"])
    wall = round(time.perf_counter() - r0, 3)
    _emit({
        "stage": "dataset", "workload": "connected_components",
        "dataset": "ldbc-sf1-shaped", "baseline_config": 2,
        "platform": platform, "num_vertices": lcsr.num_vertices,
        "num_edges": lcsr.num_edges, "wall_s": wall,
        "scale_down": ldbc_kw["scale_down"],
        "components": int(len(np.unique(comp))),
        "path": ex.last_run_info.get("path"),
    })
    _hb(f"datasets: ldbc-sf1 CC {wall}s", t0)
    del ex, lcsr, res

    g0 = time.perf_counter()
    tcsr = twitter_csr(tw_n, tw_ef)
    _hb(f"datasets: twitter-shaped proxy |V|={tcsr.num_vertices} "
        f"|E|={tcsr.num_edges} ({time.perf_counter() - g0:.1f}s)", t0)
    ex = TPUExecutor(tcsr)
    pp = PeerPressureProgram(rounds=5)
    ex.run(pp, sync_every=5)
    r0 = time.perf_counter()
    res = ex.run(pp, sync_every=5)
    cl = np.asarray(res["cluster"])
    wall = round(time.perf_counter() - r0, 3)
    _emit({
        "stage": "dataset", "workload": "peer_pressure",
        "dataset": "twitter2010-shaped", "baseline_config": 4,
        "platform": platform, "num_vertices": tcsr.num_vertices,
        "num_edges": tcsr.num_edges, "wall_s": wall,
        "clusters": int(len(np.unique(cl))),
    })
    _hb(f"datasets: twitter peer-pressure {wall}s", t0)
    del ex, tcsr, res


def _saturate_stage(t0):
    """Closed-loop saturation ramp (ISSUE 10 acceptance): offered load
    (client concurrency) doubles per level against a remote-store-backed
    server with cost-aware admission; per-level goodput, latency
    percentiles, shed rate, and brownout rung land in the artifact. The
    defense holds when goodput past saturation stays within 10% of peak
    — no congestion collapse — with every shed carrying Retry-After and
    zero hung connections."""
    import threading as _threading

    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.driver import JanusGraphClient
    from janusgraph_tpu.driver.client import RemoteError
    from janusgraph_tpu.observability import flight_recorder, registry
    from janusgraph_tpu.server import JanusGraphManager, JanusGraphServer
    from janusgraph_tpu.server.admission import AdmissionController
    from janusgraph_tpu.storage.inmemory import InMemoryStoreManager
    from janusgraph_tpu.storage.remote import RemoteStoreServer

    levels = [
        int(x) for x in os.environ.get(
            "SATURATE_LEVELS", "1,2,4,8,16,32,64"
        ).split(",")
    ]
    level_s = float(os.environ.get("SATURATE_LEVEL_S", "3.0"))
    n_vertices = int(os.environ.get("SATURATE_VERTICES", "256"))
    out_path = os.environ.get(
        "SATURATE_OUT", os.path.join(_REPO_DIR, "SATURATE_r01.json")
    )
    # simulated per-op storage-node service time (SATURATE_STORE_LAT_US):
    # with real storage latency the request handlers' concurrent reads
    # cross the adaptive gate and ride the PIPELINED framing — the r02
    # re-run proves the AIMD limiter and price book re-converge on
    # pipelined latencies (0 = loopback, the r01 configuration)
    store_lat_us = float(os.environ.get("SATURATE_STORE_LAT_US", "0"))

    # the serving path under test: remote KCVS backend (the r05 slowest
    # link) behind the query server, admission tuned for an early knee so
    # the ramp actually crosses saturation inside the level ladder
    backing = InMemoryStoreManager()
    kcvs = RemoteStoreServer(
        _LatencyManager(backing, store_lat_us / 1e6)
        if store_lat_us else backing,
        pipeline_workers=32,
    ).start()
    host, port = kcvs.address
    graph = open_graph({
        "ids.authority-wait-ms": 0.0,
        "storage.backend": "remote",
        "storage.hostname": host,
        "storage.port": port,
    })
    graph.management().make_edge_label("knows")
    tx = graph.new_transaction()
    ids = [tx.add_vertex().id for _ in range(n_vertices)]
    for i in range(n_vertices):
        a = tx.get_vertex(ids[i])
        b = tx.get_vertex(ids[(i * 7 + 1) % n_vertices])
        tx.add_edge(a, "knows", b)
    tx.commit()
    manager = JanusGraphManager()
    manager.put_graph("graph", graph)
    ctl = AdmissionController(
        initial_limit=int(os.environ.get("SATURATE_LIMIT_INIT", "4")),
        min_limit=1,
        max_limit=int(os.environ.get("SATURATE_LIMIT_MAX", "8")),
        queue_bound=int(os.environ.get("SATURATE_QUEUE", "8")),
        retry_after_base_s=0.02, retry_after_max_s=0.5,
        brownout_window_s=2.0, brownout_enter_sheds=50,
        brownout_exit_s=4.0, brownout_dwell_s=1.0,
    )
    # latency-queueing service times (storage-latency dominated) need a
    # tighter AIMD latency threshold than the CPU-bound r01 profile: the
    # decrease must fire before queue growth doubles the median
    ctl.limiter.threshold = float(
        os.environ.get("SATURATE_AIMD_THRESHOLD", "2.0")
    )
    # the observability plane rides the ramp: a 1 s sampling cadence puts
    # several history windows inside each level, the SLO engine evaluates
    # per window, and the sampler's measured self-overhead
    # (observability.history.overhead_ms) becomes an acceptance number
    from janusgraph_tpu.observability import history, slo_engine

    history.reset()
    history.configure(interval_s=1.0)
    # the continuous sampling profiler rides the ramp too (the server
    # starts it): flame windows seal in lockstep with the 1 s history
    # windows, and its measured self-cost (wall AND cpu, 1-core honest)
    # is gated in-stage below — <1% CPU or the stage fails
    from janusgraph_tpu.observability import sampling_profiler

    sampling_profiler.reset()
    sampling_profiler.configure(
        hz=float(os.environ.get("SATURATE_PROFILE_HZ", "20")),
        max_windows=256,
    )
    server = JanusGraphServer(
        manager=manager, admission=ctl, request_timeout_s=30.0,
    ).start()

    flight_recorder.reset()
    # a deep ring for the ramp: slow-span events from thousands of slowed
    # requests must not evict the brownout transitions the artifact wants
    flight_recorder.configure(capacity=8192)
    per_level = []
    hung_total = 0
    sheds_missing_retry_after = 0
    try:
        for conc in levels:
            counts = {"ok": 0, "shed": 0, "timeout": 0, "error": 0}
            lat_ms = []
            lock = _threading.Lock()
            stop_at = time.monotonic() + level_s

            def _worker(widx):
                nonlocal sheds_missing_retry_after
                client = JanusGraphClient(
                    port=server.port, retry_budget_capacity=0,
                )
                rng = widx * 31
                while time.monotonic() < stop_at:
                    rng = (rng * 1103515245 + 12345) & 0x7FFFFFFF
                    vid = ids[rng % n_vertices]
                    q0 = time.perf_counter()
                    try:
                        client.submit(
                            f"g.V({vid}).out('knows').count()",
                            deadline_ms=10_000,
                        )
                        with lock:
                            counts["ok"] += 1
                            lat_ms.append(
                                (time.perf_counter() - q0) * 1000.0
                            )
                    except RemoteError as e:
                        with lock:
                            if e.status == "shed":
                                counts["shed"] += 1
                                if e.retry_after_s is None:
                                    sheds_missing_retry_after += 1
                            elif e.status == "timeout":
                                counts["timeout"] += 1
                            else:
                                counts["error"] += 1
                        # honor the (jittered) Retry-After hint like a
                        # well-behaved client; keeps the closed loop from
                        # degenerating into a hot shed spin
                        if e.status == "shed" and e.retry_after_s:
                            time.sleep(min(e.retry_after_s, 0.1))
                    except Exception:  # noqa: BLE001 - hang bucket
                        with lock:
                            counts["error"] += 1

            threads = [
                _threading.Thread(target=_worker, args=(i,))
                for i in range(conc)
            ]
            t_level = time.monotonic()
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=level_s + 30.0)
            hung = sum(1 for th in threads if th.is_alive())
            hung_total += hung
            wall = time.monotonic() - t_level
            lat_ms.sort()
            line = {
                "offered_concurrency": conc,
                "wall_s": round(wall, 3),
                "completed": counts["ok"],
                "goodput_per_s": round(counts["ok"] / wall, 1),
                "shed": counts["shed"],
                "shed_per_s": round(counts["shed"] / wall, 1),
                "timeouts": counts["timeout"],
                "errors": counts["error"],
                "hung_connections": hung,
                "p50_ms": round(
                    lat_ms[len(lat_ms) // 2], 2
                ) if lat_ms else None,
                "p99_ms": round(
                    lat_ms[int(len(lat_ms) * 0.99)], 2
                ) if lat_ms else None,
                "admission_limit": int(
                    registry.snapshot().get(
                        "server.admission.limit", {}
                    ).get("value", 0)
                ),
                "brownout_rung": ctl.brownout.rung,
            }
            per_level.append(line)
            _hb(
                f"saturate@{conc}: {line['goodput_per_s']:.0f} ok/s "
                f"{line['shed_per_s']:.0f} shed/s p99 {line['p99_ms']}ms "
                f"rung {line['brownout_rung']}", t0,
            )
    finally:
        server.stop()
        graph.close()
        kcvs.stop()

    # saturation = the knee: the FIRST offered load reaching 95% of peak
    # goodput (closed-loop goodput is flat past the knee, so "the level
    # with max goodput" would just pick measurement noise inside the
    # plateau); acceptance compares goodput at 2x that offered load
    # against the peak
    peak = max(per_level, key=lambda r: r["goodput_per_s"])
    knee = next(
        r for r in per_level
        if r["goodput_per_s"] >= 0.95 * peak["goodput_per_s"]
    )
    knee_conc = knee["offered_concurrency"]
    twice = next(
        (r for r in per_level
         if r["offered_concurrency"] >= 2 * knee_conc),
        per_level[-1],
    )
    ratio = (
        twice["goodput_per_s"] / peak["goodput_per_s"]
        if peak["goodput_per_s"] else 0.0
    )
    brownout_events = [
        {k: e[k] for k in ("rung", "direction", "reason", "seq")}
        for e in flight_recorder.events("brownout")
    ]
    from janusgraph_tpu.storage.pipeline import pipeline_health_block

    snap = registry.snapshot()
    pipe_block = pipeline_health_block(snap)
    # history-sampler self-overhead acceptance: the TOTAL wall the
    # sampler spent across the ramp must stay under 1% of the TOTAL
    # request wall the replica served in the same span — observability
    # whose cost is a visible fraction of the serving work has no place
    # on a serving replica (ISSUE 13 acceptance)
    sample_t = snap.get("observability.history.sample", {})
    req_t = snap.get("server.request.wall", {})
    total_sample_ms = float(sample_t.get("total_ms", 0.0) or 0.0)
    total_req_ms = float(req_t.get("total_ms", 0.0) or 0.0)
    overhead_ratio = (
        total_sample_ms / total_req_ms if total_req_ms > 0 else 0.0
    )
    history_block = {
        "samples": int(sample_t.get("count", 0) or 0),
        "windows_retained": len(history.windows()),
        "mean_sample_ms": round(
            float(sample_t.get("mean_ms", 0.0) or 0.0), 4
        ),
        "total_sample_ms": round(total_sample_ms, 3),
        "last_overhead_ms": float(
            snap.get("observability.history.overhead_ms", {})
            .get("value", 0.0)
        ),
        "total_request_ms": round(total_req_ms, 1),
        "overhead_over_request_wall": round(overhead_ratio, 6),
        "ok": bool(overhead_ratio < 0.01),
    }
    slo_block = slo_engine.snapshot()
    # continuous-profiler acceptance (ISSUE 19): the sampler's measured
    # self-CPU across the ramp must stay under 1% of one core, the
    # sampler must still be accounted for (not silently dead), and the
    # merged flame (top stacks) lands in the artifact so benchdiff can
    # attribute a future regression frame-by-frame
    sampling_profiler.seal_window(seq=-1)
    prof = sampling_profiler.status()
    merged_flame = sampling_profiler.merged_stacks()
    flame_top = dict(sorted(
        merged_flame.items(), key=lambda kv: (-kv[1], kv[0])
    )[:40])
    profiler_block = {
        "hz": prof["hz"],
        "samples": prof["samples"],
        "windows_sealed": prof["windows_sealed"],
        "distinct_stacks": len(merged_flame),
        "overhead_cpu_pct": prof["overhead_cpu_pct"],
        "overhead_wall_pct": prof["overhead_wall_pct"],
        "died": prof["died"],
        "ok": bool(
            prof["overhead_cpu_pct"] < 1.0 and prof["died"] is None
        ),
    }
    report = {
        "stage": "saturate",
        "store_latency_us": store_lat_us,
        "scenario": {
            "levels": levels, "level_s": level_s,
            "vertices": n_vertices,
            "limit_init": int(os.environ.get("SATURATE_LIMIT_INIT", "4")),
            "aimd_threshold": float(
                os.environ.get("SATURATE_AIMD_THRESHOLD", "2.0")
            ),
            "limit_max": int(os.environ.get("SATURATE_LIMIT_MAX", "8")),
            "queue_bound": int(os.environ.get("SATURATE_QUEUE", "8")),
        },
        "pipeline": pipe_block,
        "history": history_block,
        "profiler": profiler_block,
        "flame": flame_top,
        "slo": slo_block,
        "levels": per_level,
        "peak_goodput_per_s": peak["goodput_per_s"],
        "peak_offered_concurrency": peak["offered_concurrency"],
        "saturation_offered_concurrency": knee_conc,
        "goodput_at_2x_saturation_per_s": twice["goodput_per_s"],
        "goodput_at_2x_offered_concurrency": twice["offered_concurrency"],
        "goodput_2x_over_peak": round(ratio, 4),
        "no_congestion_collapse": bool(ratio >= 0.9),
        "sheds_missing_retry_after": sheds_missing_retry_after,
        "hung_connections": hung_total,
        "brownout_transitions": brownout_events,
        "ok": bool(
            ratio >= 0.9
            and sheds_missing_retry_after == 0
            and hung_total == 0
            and history_block["ok"]
            and profiler_block["ok"]
        ),
    }
    with open(out_path + ".tmp", "w") as f:
        json.dump(report, f, indent=2)
    os.replace(out_path + ".tmp", out_path)
    report["artifact"] = out_path
    _emit(report)


def _stall_holding_frame(seconds: float) -> None:
    """The seeded stall body: a NAMED frame that holds the lock while
    sleeping, so the watchdog's owner_stack evidence can be asserted to
    name the frame that was actually holding."""
    time.sleep(seconds)


def _stall_forensics_stage(t0):
    """Seeded stall -> watchdog -> flight -> bundle (ISSUE 19
    acceptance): a seeded ``stalled_lock`` fault wedges an instrumented
    lock's owner mid-episode; the stall watchdog must flight a
    ``lock_convoy`` event whose owner_stack names the holding frame, a
    complete forensics bundle must land atomically on disk, and the
    fault journal must be byte-reproducible per seed (two runs, same
    seed, byte-compared)."""
    import shutil
    import tempfile
    import threading as _threading

    from janusgraph_tpu.observability import (
        bundle_writer, flight_recorder, sampling_profiler, watchdog,
    )
    from janusgraph_tpu.observability.continuous import InstrumentedLock
    from janusgraph_tpu.storage.faults import FaultPlan

    out_path = os.environ.get(
        "FLEET_STALL_OUT", os.path.join(_REPO_DIR, "FLEET_r04.json")
    )
    stall_ms = float(os.environ.get("STALL_FORENSICS_MS", "1200"))
    seed = int(os.environ.get("STALL_FORENSICS_SEED", "1234"))
    _BUNDLE_KEYS = {
        "reason", "ts", "pid", "flame_windows", "profiler", "flight",
        "timeseries", "stacks", "requests", "watchdog",
    }

    def _run_once(run_seed):
        """One seeded episode; returns (journal bytes, run report)."""
        flight_recorder.reset()
        sampling_profiler.reset()
        sampling_profiler.configure(hz=50.0, max_windows=64)
        sampling_profiler.start()
        watchdog.reset()
        watchdog.configure(interval_s=0.1, stall_s=0.4)
        bdir = tempfile.mkdtemp(prefix="jg-stall-bundle-")
        bundle_writer.reset()
        bundle_writer.configure(directory=bdir, min_interval_s=0.0)
        plan = FaultPlan(
            seed=run_seed, stall_lock_at=0, stall_lock_ms=stall_ms,
        )
        lk = InstrumentedLock("stall-forensics", watchdog=watchdog)
        watchdog.start()
        held_at = [0.0]

        def _holder():
            with lk:
                held_at[0] = time.monotonic()
                hold_ms = plan.stalled_lock(lock="stall-forensics")
                _stall_holding_frame(hold_ms / 1000.0)

        def _waiter():
            with lk:
                pass

        th_h = _threading.Thread(target=_holder, name="stall-holder")
        th_h.start()
        time.sleep(0.1)  # the holder must win the lock first
        th_w = _threading.Thread(target=_waiter, name="stall-waiter")
        th_w.start()
        # poll until the convoy flights (or the episode ends)
        detect_ms = None
        deadline = time.monotonic() + stall_ms / 1000.0 + 10.0
        while time.monotonic() < deadline:
            if flight_recorder.events("lock_convoy"):
                detect_ms = round(
                    (time.monotonic() - held_at[0]) * 1000.0, 1
                )
                break
            time.sleep(0.02)
        th_h.join(timeout=30.0)
        th_w.join(timeout=30.0)
        watchdog.stop()
        sampling_profiler.stop()
        convoys = flight_recorder.events("lock_convoy")
        bundle = bundle_writer.latest()
        tmp_left = [
            n for n in os.listdir(bdir) if n.endswith(".tmp")
        ]
        shutil.rmtree(bdir, ignore_errors=True)
        bundle_writer.reset()
        journal = json.dumps(plan.journal, sort_keys=True)
        names_frame = any(
            "_stall_holding_frame" in (e.get("owner_stack") or "")
            for e in convoys
        )
        run = {
            "seed": run_seed,
            "convoys_flighted": len(convoys),
            "detect_ms": detect_ms,
            "owner_stack_names_holding_frame": names_frame,
            "owner_stack": (
                convoys[0].get("owner_stack") if convoys else None
            ),
            "bundle_written": bundle is not None,
            "bundle_reason": bundle.get("reason") if bundle else None,
            "bundle_complete": bool(
                bundle and _BUNDLE_KEYS.issubset(bundle)
            ),
            "torn_tmp_files": len(tmp_left),
            "hung_threads": int(th_h.is_alive()) + int(th_w.is_alive()),
        }
        return journal, run

    j1, r1 = _run_once(seed)
    j2, r2 = _run_once(seed)
    runs = [r1, r2]
    byte_equal = j1 == j2
    detect = [r["detect_ms"] for r in runs if r["detect_ms"] is not None]
    report = {
        "stage": "fleet_stall_forensics",
        "seed": seed,
        "stall_ms": stall_ms,
        "runs": runs,
        "journal": json.loads(j1),
        "journal_bytes_equal": byte_equal,
        "detect_ms": max(detect) if detect else None,
        "ok": bool(
            byte_equal
            and all(
                r["convoys_flighted"] >= 1
                and r["owner_stack_names_holding_frame"]
                and r["bundle_complete"]
                and r["torn_tmp_files"] == 0
                and r["hung_threads"] == 0
                for r in runs
            )
        ),
    }
    _hb(
        f"stall-forensics: detect {report['detect_ms']}ms "
        f"journal-equal {byte_equal} ok {report['ok']}", t0,
    )
    with open(out_path + ".tmp", "w") as f:
        json.dump(report, f, indent=2)
    os.replace(out_path + ".tmp", out_path)
    report["artifact"] = out_path
    _emit(report)


def _fleet_push_stage(t0):
    """Streaming-telemetry push-vs-poll A/B (ISSUE 20 acceptance): one
    live replica pumps flight events at a fixed rate while (a) a
    poll-mode federation sees them only at tick boundaries — the PR 17
    freshness baseline — and (b) a push-mode federation receives them
    over a real ``/watch`` WebSocket the moment they flight. The seeded
    fault plan kills the replica mid-stream (after its forensics bundle
    is announced on the bus and shipped off-host) and restarts it; the
    renegotiated channel must resume from its flight cursor so ZERO
    pumped events are lost and none duplicate. Gates: push event p99
    <= 0.1x the poll interval, bus self-cost < 1% on both the wall and
    the CPU clock, and the dead replica's bundle still retrievable from
    ``GET /fleet/bundles``. Artifact FLEET_r05.json."""
    import shutil
    import tempfile
    import threading as _threading
    import urllib.request

    from janusgraph_tpu.core.graph import JanusGraphTPU
    from janusgraph_tpu.observability import (
        FleetFederation,
        bundle_writer,
        flight_recorder,
        telemetry_bus,
    )
    from janusgraph_tpu.observability.identity import (
        replica_name,
        set_replica,
    )
    from janusgraph_tpu.observability.timeseries import history
    from janusgraph_tpu.server import (
        FleetRouter,
        JanusGraphManager,
        JanusGraphServer,
    )
    from janusgraph_tpu.server.fleet import FleetFrontend
    from janusgraph_tpu.storage.faults import FaultPlan

    out_path = os.environ.get(
        "FLEET_PUSH_OUT", os.path.join(_REPO_DIR, "FLEET_r05.json")
    )
    poll_interval_s = float(os.environ.get("PUSH_POLL_INTERVAL_S", "0.5"))
    event_hz = float(os.environ.get("PUSH_EVENT_HZ", "25"))
    phase_s = float(os.environ.get("PUSH_PHASE_S", "6"))
    seed = int(os.environ.get("PUSH_SEED", "7"))
    kill_at = int(os.environ.get("PUSH_KILL_AT", "4"))
    restart_at = int(os.environ.get("PUSH_RESTART_AT", "8"))

    plan = FaultPlan(
        seed=seed, replica_kill_at=kill_at,
        replica_restart_at=restart_at,
    )
    flight_recorder.reset()
    flight_recorder.configure(capacity=8192)
    history.reset()
    telemetry_bus.reset()
    prev_identity = replica_name()
    set_replica("r0")
    bdir = tempfile.mkdtemp(prefix="jg-push-bundle-")

    graph = JanusGraphTPU({"ids.authority-wait-ms": 0.0})
    manager = JanusGraphManager()
    manager.put_graph("graph", graph)
    router = FleetRouter()
    servers = []

    def _start_server():
        server = JanusGraphServer(
            manager=manager, replica_name="r0", bundle_dir=bdir,
            request_timeout_s=30.0,
        ).start()
        servers.append(server)
        if "r0" in router.replicas():
            router.rejoin_replica("r0", "127.0.0.1", server.port)
            router.probe("r0")
        else:
            router.add_replica("r0", "127.0.0.1", server.port)
        return server

    # pump: one thread flighting `bench_push` events at event_hz; every
    # recorded (seq, wall-ts) pair is banked for the lag/loss accounting
    ev_lock = _threading.Lock()
    recorded = []  # (seq, wall ts)
    stop_pump = _threading.Event()

    def _pump():
        period = 1.0 / max(1e-6, event_hz)
        nxt = time.monotonic()
        while not stop_pump.is_set():
            try:
                e = flight_recorder.record("bench_push", bench=1)
                with ev_lock:
                    recorded.append((e["seq"], e["ts"]))
            except Exception:  # noqa: BLE001 - survive teardown races
                pass
            nxt += period
            time.sleep(max(0.0, nxt - time.monotonic()))

    def _pump_phase():
        stop_pump.clear()
        th = _threading.Thread(target=_pump, daemon=True)
        th.start()
        return th

    fed_poll = fed_push = frontend = None
    poll_lags = []
    push_seen = []  # (seq, lag_ms)
    push_lock = _threading.Lock()
    report = {"stage": "fleet_push_poll", "seed": seed}
    try:
        server = _start_server()
        router.probe()
        bundle_writer.reset()
        bundle_writer.configure(directory=bdir, min_interval_s=0.0)

        # ------------- phase A: poll baseline (tick-boundary freshness)
        # the poll transport cannot see an event before the tick that
        # scrapes past it completes — its freshness is the tick cadence
        fed_poll = FleetFederation(
            router, interval_s=poll_interval_s, push_enabled=False,
        )
        th = _pump_phase()
        accounted = 0
        t_end = time.monotonic() + phase_s
        while time.monotonic() < t_end:
            time.sleep(poll_interval_s)
            fed_poll.tick()
            tc = time.time()
            with ev_lock:
                fresh = [ts for _, ts in recorded[accounted:] if ts <= tc]
                accounted += len(fresh)
            poll_lags.extend(
                (tc - ts) * 1000.0 for ts in fresh  # graphlint: wallclock -- tick-boundary freshness lag over event stamps
            )
        stop_pump.set()
        th.join(timeout=10.0)
        poll_events = len(recorded)
        _hb(
            f"push-poll: poll baseline {len(poll_lags)} lag samples over "
            f"{poll_events} events", t0,
        )

        # ------------------- phase B: push transport with seeded chaos
        fed_push = FleetFederation(
            router, interval_s=poll_interval_s, push_enabled=True,
            bundle_min_interval_s=0.0,
        )
        frontend = FleetFrontend(router, federation=fed_push).start()
        orig_on_event = fed_push._on_push_event

        def _spy(channel, event):
            if str(event.get("category", "")) == "bench_push":
                ts = event.get("ts")
                lag_ms = (
                    (time.time() - float(ts)) * 1000.0  # graphlint: wallclock -- push freshness lag over event stamps (in-process: zero offset)
                    if isinstance(ts, (int, float)) else None
                )
                with push_lock:
                    push_seen.append((int(event.get("seq", 0)), lag_ms))
            orig_on_event(channel, event)

        fed_push._on_push_event = _spy
        fed_push.tick()  # negotiates the /watch channel; live from here
        if "r0" not in fed_push.push_status()["channels"]:
            raise RuntimeError("push channel failed to negotiate")

        bus0 = telemetry_bus.status()
        wall0 = time.monotonic()
        push_start = len(recorded)
        th = _pump_phase()
        outage = [None, None]  # [kill wall ts, reconnect wall ts]
        bundle_after_kill = None
        t_end = time.monotonic() + phase_s
        bucket = 0
        # the loop overruns phase_s only to let the restarted replica
        # renegotiate; the bucket cap bounds a reconnection that never
        # lands (gated as a failure below, not a hang)
        while (
            time.monotonic() < t_end or (outage[0] and not outage[1])
        ) and bucket < 64:
            time.sleep(poll_interval_s)
            for event in plan.fleet_hook(1):
                if event["kind"] == "replica_kill":
                    # the dying replica's pager announces its bundle on
                    # the bus on the way down; the push channel ships it
                    # off-host before the process is gone
                    bundle_writer.capture(reason="bench-kill", force=True)
                    ship_deadline = time.monotonic() + 10.0
                    while (
                        fed_push.bundles.get("r0") is None
                        and time.monotonic() < ship_deadline
                    ):
                        time.sleep(0.05)
                    outage[0] = time.time()
                    server.stop()
                    # crash detection: two consecutive probe misses
                    router.probe("r0")
                    router.probe("r0")
                    _hb(f"push-poll: killed r0 @bucket {bucket}", t0)
                elif event["kind"] == "replica_restart":
                    server = _start_server()
                    _hb(f"push-poll: restarted r0 @bucket {bucket}", t0)
            fed_push.tick()
            if outage[0] and not outage[1]:
                chan = fed_push.push_status()["channels"].get("r0")
                if chan and chan.get("connected"):
                    outage[1] = time.time()
            if outage[0] and bundle_after_kill is None:
                # off-host forensics endpoint, queried AFTER the death:
                # the shipped bundle must outlive its replica
                try:
                    with urllib.request.urlopen(
                        "http://127.0.0.1:%d/fleet/bundles?replica=r0"
                        % frontend.port, timeout=10,
                    ) as resp:
                        bundle_after_kill = json.loads(
                            resp.read().decode("utf-8")
                        )
                except Exception as e:  # noqa: BLE001 - a miss gates `ok`
                    bundle_after_kill = {
                        "status": f"{type(e).__name__}: {e}"[:200],
                    }
            bucket += 1
        stop_pump.set()
        th.join(timeout=10.0)
        with ev_lock:
            pushed = recorded[push_start:]
        # settle: tick until the resumed channel has replayed everything
        # the outage hid (or 10 s — a loss, gated below)
        settle_deadline = time.monotonic() + 10.0
        while time.monotonic() < settle_deadline:
            with push_lock:
                seen_set = {s for s, _ in push_seen}
            if all(s in seen_set for s, _ in pushed):
                break
            fed_push.tick()
            time.sleep(0.2)
        wall_ms = (time.monotonic() - wall0) * 1000.0
        bus1 = telemetry_bus.status()
    finally:
        stop_pump.set()
        if frontend is not None:
            frontend.stop()
        if fed_push is not None:
            fed_push.stop()
        router.stop()
        for s in servers:
            try:
                s.stop()
            except Exception:  # noqa: BLE001 - already stopped
                pass
        try:
            graph.close()
        except Exception:  # noqa: BLE001 - torn by the seeded kill
            pass
        bundle_writer.reset()
        telemetry_bus.reset()
        history.reset()
        flight_recorder.reset()
        set_replica(prev_identity)
        shutil.rmtree(bdir, ignore_errors=True)

    # ------------------------------------------------------- accounting
    with push_lock:
        seen = list(push_seen)
    pushed_seqs = [s for s, _ in pushed]
    seen_seqs = [s for s, _ in seen]
    seen_set = set(seen_seqs)
    lost = [s for s in pushed_seqs if s not in seen_set]
    duplicated = len(seen_seqs) - len(seen_set)
    # steady-state freshness excludes the outage window: events flighted
    # while no channel existed are REPLAYED on resume (recovery, counted
    # for loss, not for live latency)
    ts_by_seq = dict(pushed)
    outage_lo = (outage[0] - 0.1) if outage[0] else None
    outage_hi = outage[1] if outage[1] else float("inf")
    steady = [
        lag for s, lag in seen
        if lag is not None and s in ts_by_seq
        and not (
            outage_lo is not None
            and outage_lo <= ts_by_seq[s] <= outage_hi
        )
    ]
    replayed = sum(
        1 for s in pushed_seqs
        if outage_lo is not None
        and outage_lo <= ts_by_seq[s] <= outage_hi
    )

    def _p99(samples):
        if not samples:
            return float("inf")
        ss = sorted(samples)
        return round(ss[min(len(ss) - 1, int(0.99 * (len(ss) - 1)))], 3)

    poll_p99 = _p99(poll_lags)
    push_p99 = _p99(steady)
    poll_interval_ms = poll_interval_s * 1000.0
    # both self-cost clocks against elapsed wall on ONE core — the
    # sampling profiler's honest denominator (a mostly-idle process
    # makes a process-CPU denominator punish the bus for the idleness
    # around it, not for its own bill)
    bus_wall_ms = bus1["overhead_wall_ms"] - bus0["overhead_wall_ms"]
    bus_cpu_ms = bus1["overhead_cpu_ms"] - bus0["overhead_cpu_ms"]
    bus_wall_pct = bus_wall_ms / max(1e-9, wall_ms) * 100.0
    bus_cpu_pct = bus_cpu_ms / max(1e-9, wall_ms) * 100.0
    bundle_retrieved = bool(
        isinstance(bundle_after_kill, dict)
        and "status" not in bundle_after_kill
        and bundle_after_kill.get("bundle")
    )
    report.update({
        "poll_interval_ms": poll_interval_ms,
        "event_hz": event_hz,
        "phase_s": phase_s,
        "journal": plan.journal,
        "poll": {
            "events": poll_events,
            "lag_samples": len(poll_lags),
            "poll_event_p99_ms": poll_p99,
        },
        "push": {
            "events": len(pushed_seqs),
            "steady_lag_samples": len(steady),
            "replayed_through_outage": replayed,
            "events_lost": len(lost),
            "events_duplicated": duplicated,
            "outage_s": (
                round(outage[1] - outage[0], 3)  # graphlint: wallclock -- outage span over the two wall stamps bracketing it
                if outage[0] and outage[1] else None
            ),
            "push_event_p99_ms": push_p99,
        },
        "poll_event_p99_ms": poll_p99,
        "push_event_p99_ms": push_p99,
        "push_vs_poll_speedup": (
            round(poll_p99 / push_p99, 1) if push_p99 > 0 else None
        ),
        "events_lost": len(lost),
        "events_duplicated": duplicated,
        "bus_wall_overhead_ms": round(bus_wall_ms, 3),
        "bus_cpu_overhead_ms": round(bus_cpu_ms, 3),
        "bus_wall_overhead_pct": round(bus_wall_pct, 4),
        "bus_cpu_overhead_pct": round(bus_cpu_pct, 4),
        "bus_dropped": bus1["dropped"],
        "bundle_retrievable_after_kill": bundle_retrieved,
        "ok": bool(
            push_p99 <= 0.1 * poll_interval_ms
            and not lost
            and duplicated == 0
            and bus_wall_pct < 1.0
            and bus_cpu_pct < 1.0
            and bundle_retrieved
            and outage[0] is not None
            and outage[1] is not None
        ),
    })
    _hb(
        f"push-poll: push p99 {push_p99}ms vs poll p99 {poll_p99}ms "
        f"lost {len(lost)} dup {duplicated} "
        f"bus {report['bus_cpu_overhead_pct']}% cpu "
        f"ok {report['ok']}", t0,
    )
    with open(out_path + ".tmp", "w") as f:
        json.dump(report, f, indent=2)
    os.replace(out_path + ".tmp", out_path)
    report["artifact"] = out_path
    _emit(report)


def _fleet_chaos_stage(t0):
    """Fleet-level chaos certification (ISSUE 15 acceptance, extended by
    ISSUE 17): a 3-replica serving fleet over ONE shared storage backend
    takes closed-loop traffic through the consistent-hash/least-loaded
    router while the seeded fault plan kills one replica mid-traffic and
    restarts it (warm-up from the shard-checkpoint snapshot pack). The
    observability federation rides along — one tick per bucket over the
    HTTP fleet — and the artifact additionally carries the stitched
    failover forensics: the merged incident timeline (kill -> mark_dead
    -> re-pin -> warm-up phases, validated Chrome-trace document), a
    failed-over request's stitched route/attempt trace, and the scrape
    overhead gated at < 1 % of request wall."""
    import tempfile
    import threading as _threading

    from janusgraph_tpu.core.graph import JanusGraphTPU
    from janusgraph_tpu.observability import (
        FleetFederation,
        flight_recorder,
        registry,
    )
    from janusgraph_tpu.observability.identity import (
        replica_name,
        set_replica,
    )
    from janusgraph_tpu.observability.spans import tracer
    from janusgraph_tpu.observability.timeline import validate_chrome_trace
    from janusgraph_tpu.observability.timeseries import history
    from janusgraph_tpu.server import (
        FleetRouter,
        JanusGraphManager,
        JanusGraphServer,
        StateGossip,
    )
    from janusgraph_tpu.server.fleet import (
        NoReplicaAvailable,
        export_snapshot,
        warm_replica,
    )
    from janusgraph_tpu.storage.faults import FaultPlan
    from janusgraph_tpu.storage.inmemory import InMemoryStoreManager

    n_replicas = int(os.environ.get("FLEET_REPLICAS", "3"))
    workers = int(os.environ.get("FLEET_WORKERS", "8"))
    bucket_s = float(os.environ.get("FLEET_BUCKET_S", "0.5"))
    n_vertices = int(os.environ.get("FLEET_VERTICES", "256"))
    kill_at = int(os.environ.get("FLEET_KILL_AT", "6"))
    restart_at = int(os.environ.get("FLEET_RESTART_AT", "14"))
    n_buckets = int(os.environ.get("FLEET_BUCKETS", "24"))
    seed = int(os.environ.get("FLEET_SEED", "42"))
    out_path = os.environ.get(
        "FLEET_OUT", os.path.join(_REPO_DIR, "FLEET_r02.json")
    )

    shared = InMemoryStoreManager()
    base_cfg = {
        "ids.authority-wait-ms": 0.0,
        "locks.wait-ms": 0.0,
        "computer.delta": True,
    }
    graphs = [
        JanusGraphTPU(dict(base_cfg), store_manager=shared)
        for _ in range(n_replicas)
    ]
    graphs[0].management().make_edge_label("knows")
    tx = graphs[0].new_transaction()
    ids = [tx.add_vertex().id for _ in range(n_vertices)]
    for i in range(n_vertices):
        tx.add_edge(
            tx.get_vertex(ids[i]), "knows",
            tx.get_vertex(ids[(i * 7 + 1) % n_vertices]),
        )
    tx.commit()

    flight_recorder.reset()
    flight_recorder.configure(capacity=8192)
    # one process serves every replica port: the federation's
    # producer-keyed scrape cursor needs a non-empty shared identity to
    # merge the shared history ring exactly once
    prev_identity = replica_name()
    set_replica("fleet-proc")
    history.reset()
    # the stitched-failover evidence is ONE route span among the
    # thousands this stage generates; the default 256-root ring evicts
    # it within a bucket at this request rate
    tracer.configure(max_roots=8192)
    plan = FaultPlan(
        seed=seed, replica_kill_at=kill_at, replica_restart_at=restart_at,
    )
    router = FleetRouter(
        retry_budget_capacity=1e9, retry_budget_refill_per_s=1e9,
    )
    servers = {}
    gossips = {}

    def _start_replica(i, graph, warm_dir=None):
        if warm_dir:
            warm_replica(graph, warm_dir, replica=f"r{i}")
        manager = JanusGraphManager()
        manager.put_graph("graph", graph)
        server = JanusGraphServer(
            manager=manager, replica_name=f"r{i}",
            history_enabled=False, slo_enabled=False,
            request_timeout_s=30.0,
        ).start()
        gossip = StateGossip(f"r{i}", server.admission, timeout_s=2.0)
        server.gossip = gossip
        servers[f"r{i}"] = server
        gossips[f"r{i}"] = gossip
        if f"r{i}" in router.replicas():
            router.rejoin_replica(f"r{i}", "127.0.0.1", server.port)
            router.probe(f"r{i}")
        else:
            router.add_replica(f"r{i}", "127.0.0.1", server.port)
        return server

    for i, graph in enumerate(graphs):
        _start_replica(i, graph)
    urls = {
        name: f"http://127.0.0.1:{s.port}" for name, s in servers.items()
    }
    for name, gossip in gossips.items():
        gossip.set_peers([u for n2, u in urls.items() if n2 != name])
    router.probe()

    # the observability federation over the same HTTP fleet, ticked at
    # its production cadence (not per-bucket — the overhead this stage
    # certifies is the cadence a real frontend pays), scraping
    # /timeseries?raw=1 on every live replica. No sampler thread: the
    # driver ticks deterministically.
    fed_interval = float(os.environ.get("FLEET_FED_INTERVAL_S", "2.0"))
    tick_every = max(1, int(round(fed_interval / bucket_s)))
    federation = FleetFederation(router, interval_s=fed_interval)
    fleet_windows = []

    def _find_stitched():
        # a fleet.route span whose attempt children span >= 2 replicas:
        # the failed-over request as ONE stitched trace. Captured during
        # the run — the span ring evicts old roots under traffic.
        for root in reversed(tracer.recent("fleet.route")):
            attempts = [
                c for c in root.children if c.name == "fleet.attempt"
            ]
            replicas_tried = {
                a.attrs.get("replica") for a in attempts
            }
            if len(attempts) >= 2 and len(replicas_tried) >= 2:
                return {
                    "trace_id": f"{root.trace_id:016x}",
                    "verdict": root.attrs.get("verdict"),
                    "attempts": [
                        {
                            "replica": a.attrs.get("replica"),
                            "verdict": a.attrs.get("verdict"),
                        }
                        for a in attempts
                    ],
                }
        return None

    stop = _threading.Event()
    lock = _threading.Lock()
    counts = {"ok": 0, "errors": 0}
    bucket_ok = []  # per-bucket fleet completions
    errors_detail = []

    def _worker(widx):
        rng = widx * 131 + 7
        while not stop.is_set():
            rng = (rng * 1103515245 + 12345) & 0x7FFFFFFF
            vid = ids[rng % n_vertices]
            try:
                router.submit(
                    f"g.V({vid}).out('knows').count()",
                    deadline_ms=10_000, key=str(vid),
                )
                with lock:
                    counts["ok"] += 1
            except NoReplicaAvailable as e:
                with lock:
                    counts["errors"] += 1
                    if len(errors_detail) < 8:
                        errors_detail.append(str(e)[:200])
            except Exception as e:  # noqa: BLE001 - surfaced = failed
                with lock:
                    counts["errors"] += 1
                    if len(errors_detail) < 8:
                        errors_detail.append(
                            f"{type(e).__name__}: {e}"[:200]
                        )

    threads = [
        _threading.Thread(target=_worker, args=(w,))
        for w in range(workers)
    ]
    for th in threads:
        th.start()

    target_name = f"r{plan.replica_target(n_replicas)}"
    kill_bucket = restart_bucket = None
    lanes = []
    warm_dir = tempfile.mkdtemp(prefix="fleet_warm_")
    last_ok = 0
    incident = None
    trace_valid = False
    stitched = None
    try:
        for b in range(n_buckets):
            t_b = time.monotonic()
            # the seeded fleet fault plan decides this tick's events; the
            # driver executes them (kill = hard stop, the crash path)
            for event in plan.fleet_hook(n_replicas):
                victim = f"r{event['replica']}"
                if event["kind"] == "replica_kill":
                    kill_bucket = b
                    survivor = next(
                        g for i2, g in enumerate(graphs)
                        if f"r{i2}" != victim
                    )
                    # export the warm-up pack from a SURVIVOR before the
                    # kill lands — the restart path hydrates from it
                    export_snapshot(survivor, warm_dir, num_shards=2)
                    servers[victim].stop()
                    gossips[victim].stop()
                    _hb(f"fleet: killed {victim} @bucket {b}", t0)
                elif event["kind"] == "replica_restart":
                    restart_bucket = b
                    idx = int(event["replica"])
                    # a FRESH graph handle over the shared backend — the
                    # rejoining process — warmed from the checkpoint pack
                    graph = JanusGraphTPU(
                        dict(base_cfg), store_manager=shared
                    )
                    graphs[idx] = graph
                    _start_replica(idx, graph, warm_dir=warm_dir)
                    _hb(f"fleet: restarted {victim} @bucket {b}", t0)
            router.probe()
            time.sleep(max(0.0, bucket_s - (time.monotonic() - t_b)))
            # one history window per bucket (the producer cadence); one
            # federation tick per fed_interval (the scraper cadence)
            history.sample()
            if (b + 1) % tick_every == 0:
                fw = federation.tick()
                fleet_windows.append({
                    "seq": fw["seq"], "partial": fw["partial"],
                    "missing": fw["missing"], "outliers": fw["outliers"],
                    "replicas": fw["replicas"],
                })
            if stitched is None and kill_bucket is not None:
                stitched = _find_stitched()
            with lock:
                ok_now = counts["ok"]
            per_replica = {
                name: dict(h.stats)
                for name, h in router.replicas().items()
            }
            lanes.append({
                "bucket": b,
                "ok": ok_now - last_ok,
                "goodput_per_s": round((ok_now - last_ok) / bucket_s, 1),
                "replicas": {
                    name: {
                        "ok_total": st["ok"],
                        "shed_total": st["shed"],
                        "state": router.replicas()[name].state,
                        "brownout_rung": (
                            (router.replicas()[name].health.get(
                                "admission"
                            ) or {}).get("brownout_rung")
                        ),
                    }
                    for name, st in per_replica.items()
                },
            })
            last_ok = ok_now
        # forensics while the fleet is still up: the incident report
        # pulls every live replica's flight ring over HTTP
        incident = federation.incident(window_s=0)
        try:
            validate_chrome_trace(incident["trace"])
            trace_valid = True
        except Exception as e:  # noqa: BLE001 - recorded, gates `ok`
            trace_valid = False
            errors_detail.append(f"incident trace invalid: {e}"[:200])
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=10.0)
        hung = sum(1 for th in threads if th.is_alive())
        router.stop()
        for gossip in gossips.values():
            gossip.stop()
        for server in servers.values():
            try:
                server.stop()
            except Exception:  # noqa: BLE001 - already stopped
                pass
        for graph in graphs:
            try:
                graph.close()
            except Exception:  # noqa: BLE001 - victim graph may be torn
                pass
        set_replica(prev_identity)
        tracer.configure(max_roots=256)

    kb = kill_bucket if kill_bucket is not None else n_buckets // 4
    rb = restart_bucket if restart_bucket is not None else (
        3 * n_buckets // 4
    )
    pre = [r["goodput_per_s"] for r in lanes[1:kb]] or [0.0]
    during = [
        r["goodput_per_s"] for r in lanes[kb: min(kb + 4, len(lanes))]
    ] or [0.0]
    post = [r["goodput_per_s"] for r in lanes[rb + 1:]] or [0.0]
    pre_g = sum(pre) / len(pre)
    during_g = sum(during) / len(during)
    post_g = sum(post) / len(post)
    snap = registry.snapshot()
    failover_t = snap.get("fleet.router.failover", {})

    # ---- ISSUE 17: stitched failover trace + federation accounting ----
    if stitched is None:
        stitched = _find_stitched()
    scrape_wall_ms = float(
        snap.get("fleet.federation.scrape", {}).get("total_ms", 0.0)
        or 0.0
    )
    # the gate compares the CPU the federation consumed against the
    # request wall the fleet delivered: on this 1-core runner the
    # scrape's own wall is dominated by scheduler queueing behind the
    # saturating closed-loop workers (idle fetch: ~0.7 ms), which is
    # load the federation did not cause
    scrape_ms = float(
        snap.get("fleet.federation.scrape_cpu", {}).get("total_ms", 0.0)
        or 0.0
    )
    request_ms = float(
        snap.get("server.request.wall", {}).get("total_ms", 0.0) or 0.0
    )
    overhead_pct = (
        100.0 * scrape_ms / request_ms if request_ms else float("inf")
    )
    phases = [
        p["phase"] for p in (incident or {}).get("phases", [])
    ]
    # the failover grammar, reconstructed across rings: kill, then
    # mark_dead, then BOTH the re-pin and the warm-up (a restarting
    # replica hydrates before it rejoins the ring, so their mutual
    # order is the implementation's, not the grammar's)
    phases_ok = False
    if "kill" in phases:
        i = phases.index("kill")
        if "mark_dead" in phases[i + 1:]:
            j = phases.index("mark_dead", i + 1)
            tail = phases[j + 1:]
            phases_ok = "re_pin" in tail and "warm_up" in tail
    incident_block = None
    if incident is not None:
        incident_block = {
            "partial": incident["partial"],
            "missing": incident["missing"],
            "event_count": len(incident["events"]),
            "events": incident["events"][:200],
            "phases": incident["phases"],
            "trace_valid": trace_valid,
            "trace": incident["trace"],
        }
    federation_block = {
        "ticks": len(fleet_windows),
        "partial_windows": sum(
            1 for w in fleet_windows if w["partial"]
        ),
        "outlier_flags": sum(
            len(w["outliers"]) for w in fleet_windows
        ),
        "windows": fleet_windows,
        "offsets": federation.offsets.snapshot(),
        "scrape_cpu_total_ms": round(scrape_ms, 3),
        "scrape_wall_total_ms": round(scrape_wall_ms, 3),
        "request_wall_total_ms": round(request_ms, 1),
        "scrape_overhead_pct": round(overhead_pct, 4),
        "scrape_overhead_ok": bool(overhead_pct < 1.0),
        "slo": federation.slo.snapshot(),
    }
    report = {
        "stage": "fleet_chaos",
        "scenario": {
            "replicas": n_replicas, "workers": workers,
            "bucket_s": bucket_s, "buckets": n_buckets,
            "seed": seed, "target": target_name,
            "kill_bucket": kill_bucket, "restart_bucket": restart_bucket,
        },
        "fault_journal": plan.journal[:32],
        "lanes": lanes,
        "pre_kill_goodput_per_s": round(pre_g, 1),
        "during_kill_goodput_per_s": round(during_g, 1),
        "recovered_goodput_per_s": round(post_g, 1),
        "goodput_during_kill_over_prekill": round(
            during_g / pre_g if pre_g else 0.0, 4
        ),
        "goodput_recovered_over_prekill": round(
            post_g / pre_g if pre_g else 0.0, 4
        ),
        "failover_count": int(failover_t.get("count", 0) or 0),
        "failover_mean_ms": round(
            float(failover_t.get("mean_ms", 0.0) or 0.0), 2
        ),
        "failover_p99_ms": round(
            float(failover_t.get("p99_ms", 0.0) or 0.0), 2
        ),
        "router_retries": snap.get(
            "fleet.router.retries", {}
        ).get("count", 0),
        "replica_deaths": snap.get(
            "fleet.router.replica_deaths", {}
        ).get("count", 0),
        "warmup_hits": snap.get("fleet.warmup.hits", {}).get("count", 0),
        "errors_surfaced": counts["errors"],
        "errors_detail": errors_detail,
        "hung_connections": hung,
        "federation": federation_block,
        "incident": incident_block,
        "stitched_trace": stitched,
        "phases_ok": phases_ok,
        "ok": bool(
            during_g >= 0.6 * pre_g
            and post_g >= 0.9 * pre_g
            and counts["errors"] == 0
            and hung == 0
            and trace_valid
            and phases_ok
            and stitched is not None
            and overhead_pct < 1.0
        ),
    }
    with open(out_path + ".tmp", "w") as f:
        json.dump(report, f, indent=2)
    os.replace(out_path + ".tmp", out_path)
    report["artifact"] = out_path
    # lanes / incident events / fleet windows are bulky in the
    # heartbeat stream; emit a trimmed line
    emitted = {
        k: v for k, v in report.items()
        if k not in ("lanes", "incident", "federation")
    }
    if incident_block is not None:
        emitted["incident"] = {
            "partial": incident_block["partial"],
            "phases": incident_block["phases"],
            "event_count": incident_block["event_count"],
            "trace_valid": trace_valid,
        }
    emitted["federation"] = {
        k: v for k, v in federation_block.items()
        if k not in ("windows", "offsets", "slo")
    }
    _emit(emitted)


def _fleet_cdc_failover_stage(t0):
    """Durable-CDC leader failover certification (ISSUE 18): a leader
    replica streams every commit into the segmented CDC log
    (storage/cdc.py) while a follower replica bootstraps from a shard
    checkpoint, anchors a replay cursor at the checkpoint epoch, and
    pulls continuously; hinted reads (max-staleness) land on the
    follower while unhinted traffic stays leader-only. The seeded fault
    plan kills the leader mid-write-storm; the follower force-pulls the
    remaining records, promotes, and MUST end bitwise-identical to a
    fresh scan of the store — the property the whole log exists to
    guarantee. Gates, asserted in-stage: zero surfaced request errors,
    follower staleness bounded, byte-equal CSR after promotion, and the
    kill -> promote -> caught_up incident-phase grammar reconstructed
    by the observability federation."""
    import tempfile
    import threading as _threading

    from janusgraph_tpu.core.graph import JanusGraphTPU
    from janusgraph_tpu.observability import (
        FleetFederation,
        flight_recorder,
        registry,
    )
    from janusgraph_tpu.observability.identity import (
        replica_name,
        set_replica,
    )
    from janusgraph_tpu.olap.csr import load_csr, load_csr_snapshot
    from janusgraph_tpu.olap.sharded_checkpoint import save_csr_checkpoint
    from janusgraph_tpu.server import (
        FleetRouter,
        JanusGraphManager,
        JanusGraphServer,
    )
    from janusgraph_tpu.server.fleet import CDCFollower, NoReplicaAvailable
    from janusgraph_tpu.storage.cdc import CDCReader, LeaderCDCState
    from janusgraph_tpu.storage.faults import FaultPlan
    from janusgraph_tpu.storage.inmemory import InMemoryStoreManager

    workers = int(os.environ.get("FLEETCDC_WORKERS", "4"))
    bucket_s = float(os.environ.get("FLEETCDC_BUCKET_S", "0.25"))
    n_vertices = int(os.environ.get("FLEETCDC_VERTICES", "192"))
    kill_at = int(os.environ.get("FLEETCDC_KILL_AT", "8"))
    n_buckets = int(os.environ.get("FLEETCDC_BUCKETS", "20"))
    seed = int(os.environ.get("FLEETCDC_SEED", "42"))
    staleness_bound_ms = float(
        os.environ.get("FLEETCDC_STALENESS_MS", "10000")
    )
    out_path = os.environ.get(
        "FLEETCDC_OUT", os.path.join(_REPO_DIR, "FLEET_r03.json")
    )

    shared = InMemoryStoreManager()
    cdc_dir = tempfile.mkdtemp(prefix="fleet_cdc_")
    ckpt_dir = tempfile.mkdtemp(prefix="fleet_cdc_ckpt_")
    base_cfg = {
        "ids.authority-wait-ms": 0.0,
        "locks.wait-ms": 0.0,
        "computer.delta": True,
    }
    leader_cfg = dict(
        base_cfg, **{
            "storage.cdc.dir": cdc_dir,
            "storage.cdc.segment-records": 64,
        }
    )
    g_leader = JanusGraphTPU(leader_cfg, store_manager=shared)
    g_leader.management().make_edge_label("knows")
    tx = g_leader.new_transaction()
    ids = [tx.add_vertex().id for _ in range(n_vertices)]
    for i in range(n_vertices):
        tx.add_edge(
            tx.get_vertex(ids[i]), "knows",
            tx.get_vertex(ids[(i * 7 + 1) % n_vertices]),
        )
    tx.commit()
    # the follower's bootstrap pack: shard checkpoint at the seed epoch
    csr0, epoch0 = load_csr_snapshot(g_leader)
    save_csr_checkpoint(ckpt_dir, csr0, epoch0, num_shards=2)

    flight_recorder.reset()
    flight_recorder.configure(capacity=8192)
    prev_identity = replica_name()
    set_replica("fleet-proc")

    plan = FaultPlan(seed=seed, replica_kill_at=kill_at)
    # the seeded plan picks the kill target; the LEADER takes that name,
    # so the certified scenario is always leader-death, deterministically
    leader_idx = plan.replica_target(2)
    leader_name = f"r{leader_idx}"
    follower_name = f"r{1 - leader_idx}"

    g_follower = JanusGraphTPU(dict(base_cfg), store_manager=shared)
    follower = CDCFollower(
        CDCReader(cdc_dir), ckpt_dir, graph=g_follower,
        idm=g_follower.idm, name=follower_name,
        max_staleness_ms=staleness_bound_ms,
    )
    if not follower.bootstrap():
        raise RuntimeError("follower bootstrap failed")

    servers = {}

    def _start(name, graph, cdc_state):
        manager = JanusGraphManager()
        manager.put_graph("graph", graph)
        server = JanusGraphServer(
            manager=manager, replica_name=name,
            history_enabled=False, slo_enabled=False,
            request_timeout_s=30.0,
        ).start()
        server.cdc_state = cdc_state
        servers[name] = server
        return server

    _start(leader_name, g_leader, LeaderCDCState(g_leader.cdc_log))
    _start(follower_name, g_follower, follower)
    router = FleetRouter(
        retry_budget_capacity=1e9, retry_budget_refill_per_s=1e9,
    )
    for name, server in servers.items():
        router.add_replica(name, "127.0.0.1", server.port)
    router.probe()
    federation = FleetFederation(router, interval_s=bucket_s)

    stop = _threading.Event()
    writer_stop = _threading.Event()
    lock = _threading.Lock()
    counts = {"ok": 0, "errors": 0, "writes": 0}
    errors_detail = []

    def _reader(widx):
        # even workers hint a staleness budget (follower-eligible);
        # odd workers stay unhinted (leader-only by contract)
        hint = staleness_bound_ms if widx % 2 == 0 else None
        rng = widx * 131 + 7
        while not stop.is_set():
            rng = (rng * 1103515245 + 12345) & 0x7FFFFFFF
            vid = ids[rng % n_vertices]
            try:
                router.submit(
                    f"g.V({vid}).out('knows').count()",
                    deadline_ms=10_000, key=str(vid),
                    max_staleness_ms=hint,
                )
                with lock:
                    counts["ok"] += 1
            except NoReplicaAvailable as e:
                with lock:
                    counts["errors"] += 1
                    if len(errors_detail) < 8:
                        errors_detail.append(str(e)[:200])
            except Exception as e:  # noqa: BLE001 - surfaced = failed
                with lock:
                    counts["errors"] += 1
                    if len(errors_detail) < 8:
                        errors_detail.append(
                            f"{type(e).__name__}: {e}"[:200]
                        )

    def _writer():
        # the write storm: every commit lands one CDC record; the
        # leader's death interrupts this loop mid-stream
        rng = 97
        while not writer_stop.is_set():
            rng = (rng * 1103515245 + 12345) & 0x7FFFFFFF
            wtx = g_leader.new_transaction()
            for k in range(8):
                a = ids[(rng + k * 31) % n_vertices]
                b = ids[(rng + k * 53 + 1) % n_vertices]
                wtx.add_edge(
                    wtx.get_vertex(a), "knows", wtx.get_vertex(b),
                )
            wtx.commit()
            with lock:
                counts["writes"] += 1
            time.sleep(0.005)

    threads = [
        _threading.Thread(target=_reader, args=(w,)) for w in range(workers)
    ]
    wthread = _threading.Thread(target=_writer)
    for th in threads:
        th.start()
    wthread.start()

    fr_before = registry.snapshot().get(
        "fleet.router.follower_reads", {}
    ).get("count", 0)
    lanes = []
    staleness_samples = []
    kill_bucket = None
    promote_report = None
    last_ok = 0
    incident = None
    try:
        for b in range(n_buckets):
            t_b = time.monotonic()
            for event in plan.fleet_hook(2):
                if event["kind"] != "replica_kill":
                    continue
                kill_bucket = b
                # the crash path: stop the storm AND the leader, then
                # the follower promotes from the durable log alone
                writer_stop.set()
                wthread.join(timeout=10.0)
                servers[leader_name].stop()
                _hb(f"fleet-cdc: killed leader {leader_name} @b{b}", t0)
                promote_report = follower.promote()
                _hb(
                    "fleet-cdc: promoted "
                    f"{follower_name} in "
                    f"{promote_report['promote_ms']:.1f}ms "
                    f"(applied={promote_report['applied']})", t0,
                )
            router.probe()
            follower.pull()
            stale_s = follower.staleness_s()
            if stale_s != float("inf"):
                staleness_samples.append(stale_s * 1000.0)
            time.sleep(max(0.0, bucket_s - (time.monotonic() - t_b)))
            with lock:
                ok_now = counts["ok"]
            lanes.append({
                "bucket": b,
                "ok": ok_now - last_ok,
                "goodput_per_s": round((ok_now - last_ok) / bucket_s, 1),
                "staleness_ms": round(stale_s * 1000.0, 3) if (
                    stale_s != float("inf")
                ) else None,
                "follower_role": follower.role,
                "lag_records": follower.lag_records(),
            })
            last_ok = ok_now
        # the incident narrative while the survivor still serves: the
        # federation merges the live flight rings over HTTP
        incident = federation.incident(window_s=0)
    finally:
        stop.set()
        writer_stop.set()
        for th in threads:
            th.join(timeout=10.0)
        if wthread.is_alive():
            wthread.join(timeout=10.0)
        hung = sum(1 for th in threads if th.is_alive())
        router.stop()
        for server in servers.values():
            try:
                server.stop()
            except Exception:  # noqa: BLE001 - leader already dead
                pass

    # ---- the tentpole property, asserted in-stage: the promoted
    # follower's CSR is bitwise-identical to a FRESH scan of the store
    # at the same epoch (checkpoint + replayed CDC == ground truth) ----
    g_verify = JanusGraphTPU(dict(base_cfg), store_manager=shared)
    try:
        truth = load_csr(g_verify)
        fcsr = follower.csr
        bitwise_equal = all(
            (getattr(fcsr, lane) == getattr(truth, lane)).all()
            for lane in (
                "vertex_ids", "out_indptr", "in_indptr",
                "out_dst", "in_src",
            )
        )
    finally:
        g_verify.close()
        for graph in (g_leader, g_follower):
            try:
                graph.close()
            except Exception:  # noqa: BLE001 - victim graph may be torn
                pass
        set_replica(prev_identity)

    snap = registry.snapshot()
    follower_reads = int(
        snap.get("fleet.router.follower_reads", {}).get("count", 0)
        or 0
    ) - int(fr_before or 0)
    staleness_samples.sort()
    stale_p99 = (
        staleness_samples[
            min(
                len(staleness_samples) - 1,
                int(0.99 * (len(staleness_samples) - 1)),
            )
        ] if staleness_samples else float("inf")
    )
    phases = [p["phase"] for p in (incident or {}).get("phases", [])]
    # the failover grammar this stage certifies: kill, then promote,
    # then the promoted replica proves itself caught up
    phases_ok = False
    if "kill" in phases:
        i = phases.index("kill")
        tail = phases[i + 1:]
        phases_ok = "promote" in tail and "caught_up" in tail
    report = {
        "stage": "fleet_cdc_failover",
        "scenario": {
            "workers": workers, "bucket_s": bucket_s,
            "buckets": n_buckets, "seed": seed,
            "leader": leader_name, "follower": follower_name,
            "kill_bucket": kill_bucket, "vertices": n_vertices,
            "staleness_bound_ms": staleness_bound_ms,
        },
        "fault_journal": plan.journal[:32],
        "lanes": lanes,
        "writes_committed": counts["writes"],
        "cdc": follower.healthz_block(),
        "promote_ms": round(
            float(promote_report["promote_ms"]), 2
        ) if promote_report else None,
        "promote_applied": (
            promote_report["applied"] if promote_report else None
        ),
        "staleness_p99_ms": round(stale_p99, 3) if (
            stale_p99 != float("inf")
        ) else None,
        "follower_reads": follower_reads,
        "follower_read_share": round(
            follower_reads / counts["ok"] if counts["ok"] else 0.0, 4
        ),
        "rebootstraps": follower.rebootstraps,
        "bitwise_equal": bool(bitwise_equal),
        "errors_surfaced": counts["errors"],
        "errors_detail": errors_detail,
        "hung_connections": hung,
        "phases": (incident or {}).get("phases", []),
        "phases_ok": phases_ok,
        "ok": bool(
            counts["errors"] == 0
            and hung == 0
            and bitwise_equal
            and promote_report is not None
            and promote_report.get("ok")
            and stale_p99 <= staleness_bound_ms
            and phases_ok
        ),
    }
    with open(out_path + ".tmp", "w") as f:
        json.dump(report, f, indent=2)
    os.replace(out_path + ".tmp", out_path)
    report["artifact"] = out_path
    emitted = {k: v for k, v in report.items() if k != "lanes"}
    _emit(emitted)


def _oltp_stage(t0):
    """OLTP throughput micro-bench (VERDICT r4 #7): tx-path batched addEdge
    commits/s and multiQuery reads/s on the inmemory and remote backends.
    The reference publishes no OLTP numbers (SURVEY §6) — this establishes
    the framework's own regression baseline. Reference hot loops:
    StandardJanusGraph.java:674-830 (commit), StandardJanusGraphTx.java:1118
    (multiQuery). Host-side pure-Python: platform-independent."""
    import numpy as np

    from janusgraph_tpu.core.codecs import Direction
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap.generators import rmat_csr

    scale = int(os.environ.get("BENCH_OLTP_SCALE", "16"))
    edge_cap = int(os.environ.get("BENCH_OLTP_EDGE_CAP", "100000"))
    batch = 5000
    csr = rmat_csr(scale, 16)
    src = np.repeat(
        np.arange(csr.num_vertices), np.diff(csr.out_indptr)
    )[:edge_cap]
    dst = csr.out_dst[:edge_cap]

    def _measure(backend_name, cfg):
        # per-backend store latency histograms attach to the stage line
        # (reset between backends so the snapshots don't mix)
        from janusgraph_tpu.util.metrics import metrics as _reg

        _reg.reset()
        cfg = dict(cfg, **{"metrics.enabled": True})
        g = open_graph(cfg)
        g.management().make_edge_label("knows")
        v0 = time.perf_counter()
        tx = g.new_transaction()
        ids = [tx.add_vertex().id for _ in range(csr.num_vertices)]
        tx.commit()
        vertex_s = time.perf_counter() - v0

        e0 = time.perf_counter()
        commits = 0
        pending = 0
        tx = g.new_transaction()
        for i in range(len(src)):
            sv = tx.get_vertex(ids[src[i]])
            dv = tx.get_vertex(ids[dst[i]])
            tx.add_edge(sv, "knows", dv)
            pending += 1
            if pending == batch:
                tx.commit()
                commits += 1
                pending = 0
                tx = g.new_transaction()
        if pending:
            tx.commit()
            commits += 1
        else:
            tx.rollback()
        edge_s = time.perf_counter() - e0

        rng = np.random.default_rng(0)
        sample = rng.choice(ids, size=2000, replace=False)
        q0 = time.perf_counter()
        tx = g.new_transaction()
        vs = [tx.get_vertex(int(i)) for i in sample]
        tx.prefetch(vs, Direction.OUT, ("knows",))  # the multiQuery batch
        edges_read = 0
        for v in vs:
            edges_read += sum(
                1 for _ in tx.get_edges(v, Direction.OUT, ("knows",))
            )
        query_s = time.perf_counter() - q0
        tx.rollback()

        # traversal burst through the DSL so the query-digest table has
        # shapes to rank: three distinct shapes, many literals each — the
        # top-3 digests attach to this stage's artifact line
        from janusgraph_tpu.observability.profiler import digest_table

        digest_table.reset()
        src_g = g.traversal()
        for vid in sample[:40]:
            src_g.V(int(vid)).out("knows").count()
        for vid in sample[:20]:
            src_g.V(int(vid)).out("knows").out("knows").count()
        for vid in sample[:10]:
            src_g.V(int(vid)).both("knows").limit(5).to_list()
        src_g.tx.rollback()
        g.close()
        store_hists = {
            name: {
                "count": m["count"],
                "p50_ms": round(m["p50_ms"], 4),
                "p95_ms": round(m["p95_ms"], 4),
                "p99_ms": round(m["p99_ms"], 4),
                "total_ms": round(m["total_ms"], 2),
            }
            for name, m in _reg.snapshot().items()
            if m["type"] == "timer" and name.startswith(("storage.", "tx."))
        }
        line = {
            "stage": "oltp", "backend": backend_name, "scale": scale,
            "vertices": csr.num_vertices, "edges_written": len(src),
            "commit_batch": batch,
            "add_vertex_per_s": round(csr.num_vertices / vertex_s, 1),
            "add_edge_per_s": round(len(src) / edge_s, 1),
            "commits_per_s": round(commits / edge_s, 2),
            "multiquery_vertices_per_s": round(len(vs) / query_s, 1),
            "multiquery_edges_read": edges_read,
            # top-3 query digests by total cost (shape, count, total/p50/
            # p95 wall, cells) from the traversal burst above
            "telemetry": {
                "store_histograms": store_hists,
                "query_digests": digest_table.top(3),
            },
        }
        _hb(
            f"oltp[{backend_name}]: {line['add_edge_per_s']:.0f} addEdge/s "
            f"{line['commits_per_s']:.1f} commits/s "
            f"{line['multiquery_vertices_per_s']:.0f} mq-vertices/s", t0,
        )
        _emit(line)

    _measure("inmemory", {"storage.backend": "inmemory"})

    from janusgraph_tpu.storage.inmemory import InMemoryStoreManager
    from janusgraph_tpu.storage.remote import RemoteStoreServer

    server = RemoteStoreServer(InMemoryStoreManager()).start()
    host, port = server.address
    try:
        _measure("remote", {
            "storage.backend": "remote",
            "storage.hostname": host,
            "storage.port": port,
        })
    finally:
        server.stop()


class _LatencyStore:
    """Per-op simulated storage-node service time: every KCVS call pays
    a fixed sleep (media + replication + fabric RTT of a REAL storage
    node — the loopback in-process server otherwise answers in ~30 us,
    which no deployed Cassandra/HBase-class backend does). The sleep
    releases the GIL exactly like real socket/disk waits."""

    def __init__(self, inner, lat_s):
        self._inner = inner
        self._lat_s = lat_s

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def get_slice(self, *a, **k):
        time.sleep(self._lat_s)
        return self._inner.get_slice(*a, **k)

    def get_slice_multi(self, *a, **k):
        time.sleep(self._lat_s)
        return self._inner.get_slice_multi(*a, **k)

    def mutate(self, *a, **k):
        time.sleep(self._lat_s)
        return self._inner.mutate(*a, **k)


class _LatencyManager:
    def __init__(self, inner, lat_s):
        self._inner = inner
        self._lat_s = lat_s

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def open_database(self, name):
        return _LatencyStore(self._inner.open_database(name), self._lat_s)

    def mutate_many(self, *a, **k):
        time.sleep(self._lat_s)
        return self._inner.mutate_many(*a, **k)


def _oltp_spillover_stage(t0):
    """OLTP->OLAP spillover A/B (ISSUE 12 acceptance): a burst of 2/3/4-hop
    ``g.V(seeds).out('knows')^h.count()`` traversals at s16, step-walk
    (planner disabled) vs spilled (promoted onto the OLAP executor over
    the cached CSR snapshot), median of 3 timed runs each after warmup.
    Results are asserted set-equal in-stage (count AND the dedup'd
    endpoint-id set), the promotion trace rides the artifact line, and
    every cell appends to bench_artifacts/r9_spillover_ab_<ts>.jsonl."""
    import statistics as _stats

    import numpy as np

    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.observability import registry
    from janusgraph_tpu.observability.profiler import digest_table
    from janusgraph_tpu.olap.generators import rmat_csr

    scale = int(os.environ.get("BENCH_SPILLOVER_SCALE", "16"))
    edge_cap = int(os.environ.get("BENCH_SPILLOVER_EDGES", "400000"))
    n_seeds = int(os.environ.get("BENCH_SPILLOVER_SEEDS", "24"))
    batch = 10_000
    csr = rmat_csr(scale, 16)
    src = np.repeat(
        np.arange(csr.num_vertices), np.diff(csr.out_indptr)
    )[:edge_cap]
    dst = csr.out_dst[:edge_cap]

    digest_table.reset()
    g = open_graph({
        "storage.backend": "inmemory",
        "computer.spillover": True,
        "computer.spillover-min-cost-ms": float(
            os.environ.get("BENCH_SPILLOVER_MIN_COST_MS", "5")
        ),
        "computer.spillover-min-seen": 2,
    })
    g.management().make_edge_label("knows")
    b0 = time.perf_counter()
    tx = g.new_transaction()
    ids = [tx.add_vertex().id for _ in range(csr.num_vertices)]
    tx.commit()
    tx = g.new_transaction()
    pending = 0
    for i in range(len(src)):
        sv = tx.get_vertex(ids[src[i]])
        dv = tx.get_vertex(ids[dst[i]])
        tx.add_edge(sv, "knows", dv)
        pending += 1
        if pending == batch:
            tx.commit()
            pending = 0
            tx = g.new_transaction()
    if pending:
        tx.commit()
    else:
        tx.rollback()
    build_s = time.perf_counter() - b0
    _hb(
        f"oltp_spillover: built s{scale} graph ({csr.num_vertices} v, "
        f"{len(src)} e) in {build_s:.1f}s", t0,
    )

    rng = np.random.default_rng(7)
    # seed selection: moderate-fanout vertices whose 4-hop traverser
    # total (computed host-side with the same count recurrence the
    # spilled program runs) stays within the per-query traverser budget
    # — RMAT hubs explode a 2-hop walk past query.max-traversers
    deg = np.bincount(src, minlength=csr.num_vertices)
    candidates = rng.permutation(
        np.nonzero((deg >= 2) & (deg <= 32))[0]
    )
    seeds = []
    budget4 = 0.0
    for v in candidates:
        c = np.zeros(csr.num_vertices)
        c[int(v)] = 1.0
        totals = []
        for _ in range(4):
            c = np.bincount(
                dst, weights=c[src], minlength=csr.num_vertices
            )
            totals.append(c.sum())
        # per-seed AND whole-burst 4-hop budget: the step walk
        # materializes every traverser, and the burst must stay inside
        # query.max-traversers at the deepest cell
        if totals[2] >= 200 and totals[3] <= 120_000 and (
            budget4 + totals[3] <= 800_000
        ):
            seeds.append(ids[int(v)])
            budget4 += totals[3]
        if len(seeds) >= n_seeds:
            break
    planner = g.spillover_planner

    # the burst: the recurring multi-seed shape — re-running it is what
    # gives the digest table the repetitions the promotion policy needs
    def _burst_count(hops):
        t = g.traversal().V(*seeds)
        for _ in range(hops):
            t = t.out("knows")
        return t.count()

    def _burst_ids(hops):
        t = g.traversal().V(*seeds)
        for _ in range(hops):
            t = t.out("knows")
        return sorted(t.dedup().id_().to_list())

    def _spill_count():
        return registry.snapshot().get(
            "olap.spillover.spilled", {}
        ).get("count", 0)

    ts = time.strftime("%Y%m%d-%H%M%S")
    art_dir = os.path.join(_REPO_DIR, "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    art_path = os.path.join(art_dir, f"r9_spillover_ab_{ts}.jsonl")
    cells = []
    promotion_trace = []
    with open(art_path, "a") as art:
        for hops in (2, 3, 4):
            # A: the step-by-step walk (planner off). These runs also
            # feed the digest table the measured mean cost the promotion
            # policy prices the shape from.
            planner.enabled = False
            _burst_count(hops)  # warm row caches
            walk_walls = []
            for _ in range(3):
                w0 = time.perf_counter()
                walk_total = _burst_count(hops)
                walk_walls.append((time.perf_counter() - w0) * 1e3)
            walk_ids = _burst_ids(hops)
            # B: spilled. The first promoted run pays the one-time CSR
            # pack + compile (recorded as warmup), steady-state timed.
            planner.enabled = True
            before = _spill_count()
            p0 = time.perf_counter()
            _burst_count(hops)  # promotion run (count >= min-seen now)
            warm_ms = (time.perf_counter() - p0) * 1e3
            spilled_engaged = _spill_count() > before
            spill_walls = []
            for _ in range(3):
                w0 = time.perf_counter()
                spill_total = _burst_count(hops)
                spill_walls.append((time.perf_counter() - w0) * 1e3)
            _burst_ids(hops)  # brings the id-shape past min-seen
            spill_ids = _burst_ids(hops)
            promotion_trace = [
                {"digest": d, **s}
                for d, s in sorted(planner.promotion_snapshot().items())
            ]
            walk_ms = _stats.median(walk_walls)
            spill_ms = _stats.median(spill_walls)
            set_equal = (
                walk_total == spill_total and walk_ids == spill_ids
            )
            assert set_equal, (
                f"spillover A/B mismatch at {hops} hops: "
                f"walk {walk_total}/{len(walk_ids)} distinct vs "
                f"spilled {spill_total}/{len(spill_ids)} distinct"
            )
            cell = {
                "hops": hops,
                "seeds": len(seeds),
                "traversers": walk_total,
                "distinct_endpoints": len(walk_ids),
                "walk_ms": [round(w, 2) for w in walk_walls],
                "walk_median_ms": round(walk_ms, 2),
                "spill_warmup_ms": round(warm_ms, 2),
                "spill_ms": [round(w, 2) for w in spill_walls],
                "spill_median_ms": round(spill_ms, 2),
                "speedup": round(walk_ms / spill_ms, 2) if spill_ms else None,
                "spilled_engaged": spilled_engaged,
                "set_equal": set_equal,
            }
            cells.append(cell)
            art.write(json.dumps({
                "stage": "oltp_spillover", "scale": scale, **cell,
            }) + "\n")
            art.flush()
            _hb(
                f"oltp_spillover@{hops}hop: walk {walk_ms:.0f}ms vs "
                f"spilled {spill_ms:.1f}ms ({cell['speedup']}x, "
                f"{walk_total} traversers)", t0,
            )
    three = next(c for c in cells if c["hops"] == 3)
    line = {
        "stage": "oltp_spillover",
        "scale": scale,
        "vertices": csr.num_vertices,
        "edges": len(src),
        "build_s": round(build_s, 1),
        "cells": cells,
        "promotion_trace": promotion_trace,
        "spillover_counters": {
            name[len("olap.spillover."):]: m["count"]
            for name, m in registry.snapshot().items()
            if m["type"] == "counter"
            and name.startswith("olap.spillover.")
            and "." not in name[len("olap.spillover."):]
        },
        "artifact": os.path.relpath(art_path, _REPO_DIR),
        "accept_3x": bool(
            three["speedup"] and three["speedup"] >= 3.0
            and three["set_equal"] and three["spilled_engaged"]
        ),
    }
    g.close()
    _emit(line)
    _hb(
        f"oltp_spillover: 3-hop {three['speedup']}x "
        f"(>=3x: {line['accept_3x']})", t0,
    )


def _streaming_freshness_stage(t0):
    """Streaming freshness A/B (ISSUE 14 acceptance): sustained write
    bursts against a store-backed graph while a rolling PageRank keeps
    running over the snapshot. Per round: commit a bounded burst
    (<= 1% of edges), refresh the snapshot via the delta capture
    (zero store reads, olap/delta.materialize) AND via a full
    scan+repack (load_csr_snapshot), and assert the two are
    array-for-array identical — which makes every superstep over the
    refreshed arrays bitwise-identical to the repacked CSR by
    construction (additionally asserted by running PageRank on both).
    Round 1 also runs a FUSED cell: the overlay consumed superstep-side
    (base pack untouched), CC bitwise vs repack per the MIN contract.
    Reports refresh-vs-repack latency, write throughput, and the
    staleness window per round; acceptance: refresh >= 10x faster than
    the repack at <= 1% churn."""
    import statistics as _stats

    import numpy as np

    from janusgraph_tpu.core.bulk import bulk_add_edges, bulk_add_vertices
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta as _delta
    from janusgraph_tpu.olap.csr import load_csr_snapshot
    from janusgraph_tpu.olap.generators import rmat_csr
    from janusgraph_tpu.olap.programs import PageRankProgram
    from janusgraph_tpu.olap.programs.connected_components import (
        ConnectedComponentsProgram,
    )
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor
    from janusgraph_tpu.observability import registry

    scale = int(os.environ.get("BENCH_STREAM_SCALE", "20"))
    edge_cap = int(os.environ.get("BENCH_STREAM_EDGES", "2000000"))
    rounds = int(os.environ.get("BENCH_STREAM_ROUNDS", "4"))
    burst_frac = float(os.environ.get("BENCH_STREAM_BURST", "0.005"))
    pr_iters = int(os.environ.get("BENCH_STREAM_PR_ITERS", "5"))

    base_csr = rmat_csr(scale, 16)
    n = base_csr.num_vertices
    src = np.repeat(
        np.arange(n), np.diff(base_csr.out_indptr)
    )[:edge_cap]
    dst = np.asarray(base_csr.out_dst[:edge_cap], dtype=np.int64)
    g = open_graph({
        "storage.backend": "inmemory",
        "computer.delta-capture-limit": 1 << 20,
    })
    b0 = time.perf_counter()
    vids = bulk_add_vertices(g, n)
    bulk_add_edges(g, "link", vids[src], vids[dst])
    build_s = time.perf_counter() - b0
    _hb(
        f"streaming_freshness: seeded s{scale} store graph "
        f"({n} v, {len(src)} e) in {build_s:.1f}s", t0,
    )

    p0 = time.perf_counter()
    csr, epoch = load_csr_snapshot(g)
    pack0_s = time.perf_counter() - p0
    _hb(f"streaming_freshness: initial pack {pack0_s:.2f}s", t0)

    rng = np.random.default_rng(14)
    burst = max(1, int(burst_frac * len(src)))
    ts = time.strftime("%Y%m%d-%H%M%S")
    art_dir = os.path.join(_REPO_DIR, "bench_artifacts")
    os.makedirs(art_dir, exist_ok=True)
    art_path = os.path.join(art_dir, f"r11_stream_{ts}.jsonl")
    cells = []
    with open(art_path, "a") as art:
        for rnd in range(rounds):
            # -- bounded write burst (bulk columnar adds; the capture
            # decodes each committed batch vectorized)
            w0 = time.perf_counter()
            bs = rng.integers(0, n, burst)
            bd = rng.integers(0, n, burst)
            bulk_add_edges(g, "link", vids[bs], vids[bd])
            write_s = time.perf_counter() - w0
            burst_epoch_t = time.perf_counter()

            # -- A: O(delta) refresh from the capture, zero store reads
            r0 = time.perf_counter()
            got = _delta.overlay_since(g, epoch)
            assert got is not None, "capture overflowed mid-bench"
            ov, upto = got
            view = _delta.OverlayView(csr, ov, max_lane_cells=1 << 22)
            refreshed = _delta.materialize(csr, ov, idm=g.idm)
            refresh_ms = (time.perf_counter() - r0) * 1e3
            staleness_ms = (time.perf_counter() - burst_epoch_t) * 1e3
            depth = ov.size
            registry.set_gauge("olap.delta.overlay_depth", float(depth))

            # -- B: the full scan + repack the delta path replaces
            k0 = time.perf_counter()
            repack, repack_epoch = load_csr_snapshot(g)
            repack_ms = (time.perf_counter() - k0) * 1e3

            # refreshed arrays must BE the repacked arrays — then every
            # superstep over them is bitwise-identical by construction
            arrays_identical = all(
                np.array_equal(getattr(refreshed, f), getattr(repack, f))
                for f in (
                    "vertex_ids", "out_indptr", "out_dst",
                    "in_indptr", "in_src",
                )
            )
            assert arrays_identical, "delta refresh diverged from repack"
            # rolling PageRank over the fresh snapshot, asserted bitwise
            # against the repacked CSR in-stage
            pr_f = TPUExecutor(refreshed, strategy="ell").run(
                PageRankProgram(max_iterations=pr_iters)
            )
            pr_r = TPUExecutor(repack, strategy="ell").run(
                PageRankProgram(max_iterations=pr_iters)
            )
            pr_bitwise = bool(
                np.array_equal(pr_f["rank"], pr_r["rank"])
            )
            assert pr_bitwise, "refreshed PageRank diverged from repack"

            fused_cell = None
            if rnd == 0:
                # fused cell: the overlay consumed superstep-side over
                # the UNTOUCHED base pack; MIN family bitwise vs repack
                f0 = time.perf_counter()
                cc_f = TPUExecutor(csr, strategy="ell", delta=view).run(
                    ConnectedComponentsProgram(max_iterations=20)
                )
                fused_wall_ms = (time.perf_counter() - f0) * 1e3
                cc_r = TPUExecutor(repack, strategy="ell").run(
                    ConnectedComponentsProgram(max_iterations=20),
                    frontier="off",
                )
                fused_cell = {
                    "cc_bitwise": bool(np.array_equal(
                        np.asarray(cc_f["component"]),
                        np.asarray(cc_r["component"]),
                    )),
                    "wall_ms": round(fused_wall_ms, 1),
                    "lane_cells": int(sum(
                        view.lanes(True)["_meta"][k]
                        for k in ("acap", "tcap", "lcap")
                    )),
                }
                assert fused_cell["cc_bitwise"], (
                    "fused CC diverged from repack"
                )

            csr, epoch = refreshed, upto
            cell = {
                "round": rnd,
                "burst_edges": int(burst),
                "writes_per_s": round(burst / max(write_s, 1e-9), 1),
                "overlay_depth": int(depth),
                "refresh_ms": round(refresh_ms, 2),
                "repack_ms": round(repack_ms, 2),
                "speedup": round(repack_ms / max(refresh_ms, 1e-9), 2),
                "staleness_window_ms": round(staleness_ms, 2),
                "arrays_identical": arrays_identical,
                "pagerank_bitwise": pr_bitwise,
                "fused": fused_cell,
            }
            cells.append(cell)
            art.write(json.dumps({
                "stage": "streaming_freshness", "scale": scale, **cell,
            }) + "\n")
            art.flush()
            _hb(
                f"streaming_freshness r{rnd}: refresh "
                f"{refresh_ms:.0f}ms vs repack {repack_ms:.0f}ms "
                f"({cell['speedup']}x), {depth} records", t0,
            )
    med_refresh = _stats.median(c["refresh_ms"] for c in cells)
    med_repack = _stats.median(c["repack_ms"] for c in cells)
    speedup = med_repack / max(med_refresh, 1e-9)
    line = {
        "stage": "streaming_freshness",
        "scale": scale,
        "vertices": n,
        "edges": len(src),
        "burst_fraction": burst_frac,
        "build_s": round(build_s, 1),
        "initial_pack_s": round(pack0_s, 2),
        "cells": cells,
        "refresh_median_ms": round(med_refresh, 2),
        "repack_median_ms": round(med_repack, 2),
        "refresh_speedup": round(speedup, 2),
        "writes_per_s": round(
            _stats.median(c["writes_per_s"] for c in cells), 1
        ),
        "staleness_window_ms": round(
            _stats.median(c["staleness_window_ms"] for c in cells), 2
        ),
        "delta_counters": {
            name[len("olap.delta."):]: m.get("count", m.get("value"))
            for name, m in registry.snapshot().items()
            if name.startswith("olap.delta.")
        },
        "artifact": os.path.relpath(art_path, _REPO_DIR),
        "accept_10x": bool(
            speedup >= 10.0
            and all(c["arrays_identical"] for c in cells)
            and all(c["pagerank_bitwise"] for c in cells)
        ),
    }
    g.close()
    _emit(line)
    _hb(
        f"streaming_freshness: refresh {speedup:.1f}x faster than "
        f"repack (>=10x: {line['accept_10x']})", t0,
    )


def _oltp_pipeline_stage(t0):
    """Pipelined-vs-synchronous wire framing A/B (ISSUE 11 acceptance):
    a closed-loop multiquery workload (per iteration: one existence-
    probe getSlice, one mutate, and every 8th iteration a 16-key
    multi-slice prefetch) against a remote KCVS server, swept over
    offered in-flight depth (worker threads) at a simulated storage-node
    service time. The synchronous baseline is the PR 1 framing
    (pipeline=False) at the default 4-connection pool; the pipelined
    path multiplexes every in-flight op over 2 sockets. Each level
    records achieved throughput, wire frames/op, coalesce ratio, and
    in-flight depth. Zero-latency cells ride along for transparency:
    in-process loopback on this host is GIL-bound, so the adaptive gate
    keeps the sync path there (~1.0x by design)."""
    import threading as _threading

    from janusgraph_tpu.observability import registry
    from janusgraph_tpu.storage.inmemory import InMemoryStoreManager
    from janusgraph_tpu.storage.kcvs import KeySliceQuery, SliceQuery
    from janusgraph_tpu.storage.remote import (
        RemoteStoreManager,
        RemoteStoreServer,
    )

    lat_us = float(os.environ.get("BENCH_PIPE_LAT_US", "2000"))
    depths = [
        int(x) for x in os.environ.get(
            "BENCH_PIPE_DEPTHS", "1,8,16,32,64"
        ).split(",")
    ]
    iters = int(os.environ.get("BENCH_PIPE_ITERS", "40"))

    def _measure(pipeline, nthreads, lat_s, iters_n, with_multi=False):
        registry.reset()
        backing = InMemoryStoreManager()
        server = RemoteStoreServer(
            _LatencyManager(backing, lat_s) if lat_s else backing,
            pipeline_workers=64,
        ).start()
        mgr = RemoteStoreManager(*server.address, pipeline=pipeline)
        store = mgr.open_database("edgestore")
        seed_keys = [f"seed{i:03d}".encode() for i in range(64)]
        for k in seed_keys:
            store.mutate(k, [(b"c", b"v")], [], None)
        # warm-up outside the timed window: dials the sockets, settles
        # the adaptive gate's service-time EWMA, and (pipelined) brings
        # the mux out of its negotiation bootstrap — both paths equally
        if nthreads > 1:
            warm = [
                _threading.Thread(
                    target=lambda i=i: [
                        store.get_slice(
                            KeySliceQuery(
                                seed_keys[i % 64], SliceQuery(b"", None)
                            ), None,
                        ) for _ in range(6)
                    ],
                )
                for i in range(nthreads)
            ]
            for th in warm:
                th.start()
            for th in warm:
                th.join()
        errs = []
        ops_done = [0]

        def worker(i):
            n = 0
            try:
                for j in range(iters_n):
                    if with_multi:
                        # prefetch shape: one 16-key multiQuery batch —
                        # ALREADY amortized on the wire, so both framings
                        # pay ~one service time per batch (recorded for
                        # transparency; expect ~1x)
                        res = store.get_slice_multi(
                            seed_keys[:16], SliceQuery(b"", None), None
                        )
                        assert len(res) == 16
                        n += 16
                        continue
                    # per-op stream: the existence-probe getSlice and
                    # point mutate — the one-op-per-roundtrip traffic
                    # the pipelined framing exists to batch
                    k = f"w{i}-{j:03d}".encode()
                    store.mutate(k, [(b"c", b"v")], [], None)
                    got = store.get_slice(
                        KeySliceQuery(k, SliceQuery(b"", None)), None
                    )
                    assert got == [(b"c", b"v")]
                    n += 2
            except Exception as e:  # noqa: BLE001 - surfaced in the line
                errs.append(f"{type(e).__name__}: {e}")
            ops_done[0] += n

        threads = [
            _threading.Thread(target=worker, args=(i,))
            for i in range(nthreads)
        ]
        stop_sampler = _threading.Event()
        inflight_samples = []

        def _sampler():
            while not stop_sampler.is_set():
                mux = mgr._mux
                if mux is not None:
                    inflight_samples.append(mux.in_flight())
                stop_sampler.wait(0.01)

        sampler = _threading.Thread(target=_sampler, daemon=True)
        w0 = time.perf_counter()
        for th in threads:
            th.start()
        sampler.start()
        for th in threads:
            th.join()
        stop_sampler.set()
        sampler.join(timeout=1.0)
        wall = time.perf_counter() - w0
        if mgr._mux is not None:
            mgr._mux.flush_stats()
        snap = registry.snapshot()

        def _cnt(name):
            return snap.get(name, {}).get("count", 0)

        p_ops = _cnt("storage.remote.pipeline.ops")
        frames = _cnt("storage.remote.pipeline.wire_frames")
        mgr.close()
        server.stop()
        return {
            "ops_per_s": round(ops_done[0] / wall, 1),
            "wall_s": round(wall, 3),
            "ops": ops_done[0],
            "pipelined_ops": p_ops,
            "wire_frames": frames,
            "frames_per_op": round(frames / p_ops, 3) if p_ops else None,
            "coalesce_ratio": round(p_ops / frames, 3) if frames else None,
            "in_flight_peak": max(inflight_samples, default=0),
            "in_flight_mean": round(
                sum(inflight_samples) / len(inflight_samples), 1
            ) if inflight_samples else 0,
            "errors": errs[:3],
        }

    levels = []
    for depth in depths:
        sync = _measure(False, depth, lat_us / 1e6, iters)
        pipe = _measure(True, depth, lat_us / 1e6, iters)
        if depth == depths[-1]:
            # one repetition pass on the acceptance cell: medians, not
            # single lucky runs (1-core host, noisy neighbors)
            import statistics as _stats

            sync_reps = [sync["ops_per_s"]] + [
                _measure(False, depth, lat_us / 1e6, iters)["ops_per_s"]
                for _ in range(2)
            ]
            pipe_reps = [pipe["ops_per_s"]] + [
                _measure(True, depth, lat_us / 1e6, iters)["ops_per_s"]
                for _ in range(2)
            ]
            sync["ops_per_s"] = round(_stats.median(sync_reps), 1)
            pipe["ops_per_s"] = round(_stats.median(pipe_reps), 1)
            sync["reps"] = [round(v, 1) for v in sync_reps]
            pipe["reps"] = [round(v, 1) for v in pipe_reps]
        speedup = (
            pipe["ops_per_s"] / sync["ops_per_s"]
            if sync["ops_per_s"] else None
        )
        levels.append({
            "offered_depth": depth,
            "sync": sync,
            "pipelined": pipe,
            "speedup": round(speedup, 3) if speedup else None,
        })
        _hb(
            f"oltp_pipeline@depth={depth}: sync {sync['ops_per_s']:.0f} "
            f"vs pipelined {pipe['ops_per_s']:.0f} ops/s "
            f"({speedup:.2f}x, coalesce "
            f"{pipe['coalesce_ratio']})", t0,
        )
    # transparency cells: (a) loopback zero latency — the adaptive gate
    # keeps the sync path (ratio ~1.0 by design on a GIL-bound host);
    # (b) the prefetch/multiQuery batch shape — already amortized on the
    # wire, both framings pay ~one service time per 16-key batch
    z_sync = _measure(False, 16, 0.0, iters)
    z_pipe = _measure(True, 16, 0.0, iters)
    m_sync = _measure(False, 16, lat_us / 1e6, 12, with_multi=True)
    m_pipe = _measure(True, 16, lat_us / 1e6, 12, with_multi=True)
    best = max(levels, key=lambda r: r["speedup"] or 0)
    line = {
        "stage": "oltp_pipeline",
        "storage_latency_us": lat_us,
        "iters_per_thread": iters,
        "pipeline_defaults": {
            "connections": 2, "depth": 128, "max_batch": 64,
            "coalesce_us": 150.0, "sync_pool_size": 4,
        },
        "depth_sweep": levels,
        "zero_latency": {
            "sync": z_sync, "pipelined": z_pipe,
            "ratio": round(
                z_pipe["ops_per_s"] / z_sync["ops_per_s"], 3
            ) if z_sync["ops_per_s"] else None,
        },
        "prefetch_batch_cell": {
            "sync": m_sync, "pipelined": m_pipe,
            "ratio": round(
                m_pipe["ops_per_s"] / m_sync["ops_per_s"], 3
            ) if m_sync["ops_per_s"] else None,
            "note": "16-key multiQuery batches are already amortized "
                    "on the wire; pipelining targets the per-op stream",
        },
        "peak_speedup": best["speedup"],
        "peak_offered_depth": best["offered_depth"],
        "accept_3x": bool(best["speedup"] and best["speedup"] >= 3.0),
    }
    _emit(line)
    _hb(
        f"oltp_pipeline: peak {best['speedup']:.2f}x at depth "
        f"{best['offered_depth']} (>=3x: {line['accept_3x']})", t0,
    )


def _pallas_stage(jax, pr_iters, t0):
    import numpy as np

    from janusgraph_tpu.olap.generators import rmat_csr
    from janusgraph_tpu.olap.programs import PageRankProgram
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    csr = rmat_csr(16, 16)
    prog = PageRankProgram(max_iterations=pr_iters, tol=0.0)
    res = {}
    times = {}
    for strat in ("ell", "pallas"):
        ex = TPUExecutor(csr, strategy=strat)
        ex.run(prog)
        r0 = time.perf_counter()
        out = ex.run(prog, sync_every=pr_iters)
        jax.block_until_ready(out["rank"])
        times[strat] = time.perf_counter() - r0
        res[strat] = np.asarray(out["rank"])
        _hb(f"pallas stage: {strat} {times[strat]:.3f}s", t0)
    if ex.last_run_info["pallas_interpret"]:
        raise RuntimeError("the Pallas kernel was interpreted, not compiled")
    max_rel = float(
        np.max(np.abs(res["pallas"] - res["ell"]) / np.maximum(res["ell"], 1e-12))
    )
    # both paths sum in float32 and differ only in the order of the sums
    # (tile order vs bucket tree): 1e-4 relative is ~100x that noise and
    # 25x under what a bfloat16-rounded dot would show (2.8e-3, PR 21)
    if max_rel >= 1e-4:
        raise RuntimeError(f"pallas vs ell max relative diff {max_rel:.3e}")
    _emit({
        "stage": "pallas",
        "ok": True,
        "scale": 16,
        "ell_wall_s": round(times["ell"], 3),
        "pallas_wall_s": round(times["pallas"], 3),
        "max_rel_diff_vs_ell": max_rel,
    })


def main() -> int:
    if "--worker" in sys.argv:
        return worker()
    return supervise()


if __name__ == "__main__":
    sys.exit(main())
