"""Traffic driver `gap-trials`: the GAP Benchmark Suite's trials of a
kernel that takes a few sources a trial (BC: 4). The generated edge list is
adopted warm; one analyst then runs one trial at a time through
graph.compute().program(p).submit(), the trial's sources as ONE submit,
each timed from submit() to `result_state` resident on the host.
Parameters (the traffic file): `program`, `result_state`, `reference`,
`sources` (`draw`: the stream of `[structure_seed, draw]` they are drawn
from, `count`, `per_trial`), `warmup_submits`, `traced_seconds`.

The sources are the first `count` of a draw among the structure's
vertices with at least one edge that is no self loop (one simple edge),
the same vertices of the structure for every --seed under its ids; trial
i takes the i-th `per_trial` of them, and the trials cycle in that fixed
order.

Reports `submit_p50_s` as `graph500-search` does: the median over the
trials of each trial's median. Every trial of the window is checked
against the reference, computed once a trial after the window; the notes
say the largest relative error seen and the vertices where both read
exactly 0."""

from __future__ import annotations

import contextlib
import statistics
import time

import numpy as np

from data import EdgeList, rmat_edges


def trial_sources(config, data, spec) -> list:
    """The trials' sources, as ids under this seed: tuples of
    `per_trial`."""
    proper = data.src != data.dst
    degree = (np.bincount(data.src[proper], minlength=data.n)
              + np.bincount(data.dst[proper], minlength=data.n))
    drawn = data.perm[np.random.default_rng(
        [config["structure_seed"], spec["draw"]]).permutation(data.n)]
    chosen = [int(v) for v in drawn[degree[drawn] > 0][:spec["count"]]]
    k = spec["per_trial"]
    return [tuple(chosen[i:i + k]) for i in range(0, len(chosen), k)]


def setup(run):
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta, programs
    from janusgraph_tpu.olap.csr import csr_from_edges

    cfg, mix = run.config, run.traffic
    with run.span("generate"):
        data = EdgeList(*rmat_edges(
            run.scale, cfg["edge_factor"], cfg["structure_seed"], run.seed))
    graph = open_graph({"storage.backend": cfg["backend"],
                        **run.graph_options})
    with run.span("snapshot"):
        csr = csr_from_edges(data.n, data.src, data.dst)
        delta.get_snapshot(graph).adopt(csr, graph.backend.mutation_epoch())
    run.say(f"adopted R-MAT scale {run.scale}: {data.n} vertices, {data.m} "
            f"edges, digest {data.digest()} (generate "
            f"{run.spans['generate']:.1f}s, csr + adopt "
            f"{run.spans['snapshot']:.1f}s)")
    trials = trial_sources(cfg, data, mix["sources"])
    state = {
        "graph": graph, "data": data, "results": [], "trials": trials,
        "program": getattr(programs, mix["program"]),
    }
    with run.span("warmup"):
        for i in range(mix["warmup_submits"]):
            sources = trials[i % len(trials)]
            wall, _, info = _trial(run, state, sources)
            if i == 0:
                run.spans["first_submit"] = wall
            tiers = sorted({(t["F_cap"], t["E_cap"], t["wide"])
                            for t in info.get("tiers", [])})
            run.say(f"warm-up trial {i + 1} sources={list(sources)}: "
                    f"{wall:.3f}s path={info.get('path')} "
                    f"levels={info.get('levels')} "
                    f"rounds={info.get('forward_rounds')}+"
                    f"{info.get('backward_rounds')} "
                    f"wide={info.get('wide_rounds')} "
                    f"retraces={info.get('retraces')} tiers={tiers}")
    run.shapes = {"vertices": data.n, "edges": data.m,
                  "closure_slots": info["closure_slots"],
                  "sources": len(trials[0])}
    run.notes["run_info"] = {
        k: info.get(k) for k in (
            "path", "supersteps", "pad_ratio", "h2d_arg_bytes", "d2h_bytes",
            "retraces", "routing", "platform", "levels", "forward_rounds",
            "backward_rounds", "rounds", "wide_rounds", "relaxed_slots",
            "tier_slots", "closure_slots",
        )
    }
    return state


def _trial(run, state, sources):
    """One trial to a host-resident result: (wall, array, info)."""
    program = state["program"](sources=sources)
    t = time.perf_counter()
    with run.annotate("submit"):
        result = state["graph"].compute().program(program).submit()
    with run.annotate("fetch"):
        array = np.asarray(result.states[run.traffic["result_state"]])
    return time.perf_counter() - t, array, result.run_info


def measure(run, state):
    trials, results = state["trials"], state["results"]
    cut = run.window_opened + run.seconds
    # the traced stretch: whole trials from the second on, until
    # `traced_seconds` have passed, so the count of trials is exact
    tracing = contextlib.ExitStack()
    trace_due, traced_from, traced = run.trace, None, 0
    i = 0
    while time.perf_counter() < cut:
        if trace_due and (i >= 1 or run.seconds < 2):
            tracing.enter_context(run.traced())
            trace_due, traced_from = False, time.perf_counter()
        sources = trials[i % len(trials)]
        wall, array, _ = _trial(run, state, sources)
        results.append((sources, wall, array))
        i += 1
        if traced_from is not None:
            traced += 1
            if time.perf_counter() - traced_from >= run.traffic["traced_seconds"]:
                tracing.close()
                traced_from = None
    tracing.close()
    if run.trace:
        run.counts["trials_traced"] = traced
    run.counts["requests"] = len(results)
    walls = [w for _, w, _ in results]
    run.say(f"{len(walls)} trials in the window, walls "
            f"{min(walls):.4f}..{max(walls):.4f}s")
    by_trial = {}
    for sources, wall, _ in results:
        by_trial.setdefault(sources, []).append(wall)
    per_trial = {s: statistics.median(ws) for s, ws in by_trial.items()}
    run.notes["per_trial_median_s"] = {
        str(list(s)): w for s, w in per_trial.items()}
    run.notes["walls_s"] = walls
    return {"submit_p50_s": statistics.median(per_trial.values())}


def check(run, state):
    """Every trial of the window against the reference, computed once a
    trial."""
    reference = run.reference(run.traffic["reference"])
    by_reason, want, worst, zeros = {}, {}, 0.0, None
    for sources, _, got in state["results"]:
        if sources not in want:
            want[sources] = reference.expect(state["data"], sources=sources)
        error, exact_zeros = reference.errors(got, want[sources])
        worst = max(worst, error)
        zeros = exact_zeros if zeros is None else min(zeros, exact_zeros)
        if not reference.agrees(got, want[sources]):
            by_reason["wrong-answer"] = by_reason.get("wrong-answer", 0) + 1
    run.notes["max_rel_error"] = worst
    run.notes["exact_zeros"] = zeros
    run.say(f"{len(state['results'])} trials against reference "
            f"{run.traffic['reference']!r} over {len(want)} trials: largest "
            f"relative error {worst!r}, {zeros} vertices exactly 0 in both")
    return {"attempted": len(state["results"]),
            "failed": sum(by_reason.values()), "by_reason": by_reason}


def teardown(run, state):
    state["graph"].close()
