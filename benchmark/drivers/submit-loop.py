"""Traffic driver `submit-loop`: one analyst submitting a vertex program
back to back through graph.compute().program(p).submit() on a snapshot
adopted warm, each submit timed from submit() to the result resident on
the host. Parameters (the traffic file): `program` (a class of
`janusgraph_tpu.olap.programs`), `args`, `result_state`, `reference`,
`roots` (null, or how many vertices with at least `min_out_degree` become
values of which constructor parameter: the same vertices of the structure
for every seed, under that seed's ids and cycled in that seed's order),
`warmup_submits`, `traced_seconds`.

Reports `submit_p50_s`: the median submit wall; with roots, the median
over the roots of each root's median, so that the number does not depend
on which roots the cut of the window left with one sample more."""

from __future__ import annotations

import contextlib
import statistics
import time

import numpy as np

from data import EdgeList, rmat_edges


def setup(run):
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta, programs
    from janusgraph_tpu.olap.csr import csr_from_edges

    cfg, mix = run.config, run.traffic
    with run.span("generate"):
        data = EdgeList(*rmat_edges(
            run.scale, cfg["edge_factor"], cfg["structure_seed"], run.seed))
    run.shapes = {"vertices": data.n, "edges": data.m}
    graph = open_graph({"storage.backend": cfg["backend"],
                        **run.graph_options})
    with run.span("snapshot"):
        # the way a fleet replica is warmed (server/fleet.warm_replica):
        # the snapshot adopts a ready CSR, and submits read no store
        csr = csr_from_edges(data.n, data.src, data.dst)
        delta.get_snapshot(graph).adopt(csr, graph.backend.mutation_epoch())
    run.say(f"adopted R-MAT scale {run.scale}: {data.n} vertices, {data.m} "
            f"edges, digest {data.digest()} (generate "
            f"{run.spans['generate']:.1f}s, csr + adopt "
            f"{run.spans['snapshot']:.1f}s)")

    roots = [None]
    if mix.get("roots"):
        spec = mix["roots"]
        # uniform over the structure's vertices, so every seed searches
        # from the same roots and meets the same frontier tiers
        drawn = data.perm[np.random.default_rng(
            [cfg["structure_seed"], 1]).permutation(data.n)]
        able = drawn[data.out_degree[drawn] >= spec["min_out_degree"]]
        order = np.random.default_rng([run.seed, 1]).permutation(spec["count"])
        roots = [int(r) for r in able[:spec["count"]][order]]
    state = {
        "graph": graph, "data": data, "roots": roots, "results": [],
        "program": getattr(programs, mix["program"]),
    }
    with run.span("warmup"):
        for i in range(mix["warmup_submits"]):
            root = roots[i % len(roots)]
            wall, _, info = _submit(run, state, root)
            if i == 0:
                run.spans["first_submit"] = wall
            run.say(f"warm-up submit {i + 1} root={root}: {wall:.3f}s "
                    f"path={info.get('path')} "
                    f"strategy={info.get('strategy_resolved')} "
                    f"supersteps={info.get('supersteps')} "
                    f"first_dispatch_s={info.get('first_dispatch_s')} "
                    f"tiers={[(t['F_cap'], t['E_cap']) for t in info.get('tiers', [])]}")
    run.notes["run_info"] = {
        k: info.get(k) for k in (
            "path", "strategy_resolved", "supersteps", "pad_ratio",
            "h2d_arg_bytes", "d2h_bytes", "retraces", "routing", "platform",
        )
    }
    return state


def _program_args(run, root) -> dict:
    args = dict(run.traffic["args"])
    if root is not None:
        args[run.traffic["roots"]["param"]] = root
    return args


def _submit(run, state, root):
    """One submit to a host-resident result: (wall, array, run_info)."""
    program = state["program"](**_program_args(run, root))
    t = time.perf_counter()
    with run.annotate("submit"):
        result = state["graph"].compute().program(program).submit()
    with run.annotate("fetch"):
        array = np.asarray(result.states[run.traffic["result_state"]])
    return time.perf_counter() - t, array, result.run_info


def measure(run, state):
    roots, results = state["roots"], state["results"]
    cut = run.window_opened + run.seconds
    # the traced stretch: whole submits from the second on, until
    # `traced_seconds` have passed, so the count of supersteps is exact
    tracing = contextlib.ExitStack()
    trace_due, traced_from, supersteps_traced = run.trace, None, 0
    i = 0
    while time.perf_counter() < cut:
        if trace_due and (i >= 1 or run.seconds < 2):
            tracing.enter_context(run.traced())
            trace_due, traced_from = False, time.perf_counter()
        root = roots[i % len(roots)]
        wall, array, info = _submit(run, state, root)
        results.append((root, wall, array))
        i += 1
        if traced_from is not None:
            supersteps_traced += info["supersteps"]
            if time.perf_counter() - traced_from >= run.traffic["traced_seconds"]:
                tracing.close()
                traced_from = None
    tracing.close()
    if run.trace:
        run.counts["supersteps_traced"] = supersteps_traced
    walls = [w for _, w, _ in results]
    run.counts["requests"] = len(results)
    run.say(f"{len(walls)} submits in the window, walls "
            f"{min(walls):.4f}..{max(walls):.4f}s")
    by_root = {}
    for root, wall, _ in results:
        by_root.setdefault(root, []).append(wall)
    per_root = {r: statistics.median(ws) for r, ws in by_root.items()}
    run.notes["per_root_median_s"] = {str(r): w for r, w in per_root.items()}
    run.notes["walls_s"] = walls
    return {"submit_p50_s": statistics.median(per_root.values())}


def check(run, state):
    """Every result of the window against the reference, root by root."""
    reference = run.reference(run.traffic["reference"])
    by_reason, want = {}, {}
    for root, _, got in state["results"]:
        if root not in want:
            want[root] = reference.expect(
                state["data"], **_program_args(run, root)
            )
        if not reference.agrees(got, want[root]):
            by_reason["wrong-answer"] = by_reason.get("wrong-answer", 0) + 1
    run.say(f"{len(state['results'])} results against reference "
            f"{run.traffic['reference']!r} over {len(want)} roots "
            f"({run.spans.get('check', 0):.1f}s)")
    return {"attempted": len(state["results"]),
            "failed": sum(by_reason.values()), "by_reason": by_reason}


def teardown(run, state):
    state["graph"].close()
