"""Traffic driver `graph500-search`: Graph500 v3 kernel 3 as the
specification runs it. The Kronecker edge list of kernel 1 with one weight
in [0, 1) per generated edge is adopted warm (weights and all); one analyst
then searches from one key at a time through
graph.compute().program(p).submit(), each search timed from submit() to
EVERY state of `result_states` (distance and parent) resident on the host.
Parameters (the traffic file): `program`, `args`, `result_states`,
`reference`, `roots` (`param`, `count`: how many search keys), `warmup_submits`,
`traced_seconds`.

The weights draw from `[structure_seed, 3]` in the generator's order and
the search keys from `[structure_seed, 2]` among the structure's vertices
with an edge that is no self loop (Graph500's rule), so every --seed runs
the same weighted structure and the same searches under other ids; the
keys cycle in that fixed order.

Reports `submit_p50_s` as `submit-loop` does: the median over the keys of
each key's median. Every search must end because nothing changed, never by
`max_iterations`: asserted search by search."""

from __future__ import annotations

import contextlib
import statistics
import time

import numpy as np

from data import EdgeList, rmat_edges


def edge_weights(config, m: int) -> np.ndarray:
    """One float32 weight uniform in [0, 1) per generated edge, in the
    generator's order, from the configuration's structure seed alone."""
    return np.random.default_rng(
        [config["structure_seed"], 3]).random(m, dtype=np.float32)


def search_keys(config, data, count: int) -> list:
    """The first `count` of a structure-seeded draw among the vertices with
    at least one edge that is no self loop, as ids under this seed."""
    proper = data.src != data.dst
    degree = (np.bincount(data.src[proper], minlength=data.n)
              + np.bincount(data.dst[proper], minlength=data.n))
    drawn = data.perm[np.random.default_rng(
        [config["structure_seed"], 2]).permutation(data.n)]
    return [int(v) for v in drawn[degree[drawn] > 0][:count]]


def setup(run):
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta, programs
    from janusgraph_tpu.olap.csr import csr_from_edges

    cfg, mix = run.config, run.traffic
    with run.span("generate"):
        data = EdgeList(*rmat_edges(
            run.scale, cfg["edge_factor"], cfg["structure_seed"], run.seed))
        data.weight = edge_weights(cfg, data.m)
    run.shapes = {"vertices": data.n, "edges": data.m}
    graph = open_graph({"storage.backend": cfg["backend"],
                        **run.graph_options})
    with run.span("snapshot"):
        csr = csr_from_edges(data.n, data.src, data.dst, weights=data.weight)
        delta.get_snapshot(graph).adopt(csr, graph.backend.mutation_epoch())
    run.say(f"adopted weighted R-MAT scale {run.scale}: {data.n} vertices, "
            f"{data.m} edges, digest {data.digest()} (generate "
            f"{run.spans['generate']:.1f}s, csr + adopt "
            f"{run.spans['snapshot']:.1f}s)")
    state = {
        "graph": graph, "data": data, "results": [],
        "roots": search_keys(cfg, data, mix["roots"]["count"]),
        "program": getattr(programs, mix["program"]),
    }
    with run.span("warmup"):
        for i in range(mix["warmup_submits"]):
            root = state["roots"][i % len(state["roots"])]
            wall, _, info = _search(run, state, root)
            if i == 0:
                run.spans["first_submit"] = wall
            tiers = sorted({(t["F_cap"], t["E_cap"])
                            for t in info.get("tiers", [])})
            run.say(f"warm-up search {i + 1} key={root}: {wall:.3f}s "
                    f"path={info.get('path')} rounds={info.get('rounds')} "
                    f"relaxed_slots={info.get('relaxed_slots')} "
                    f"tier_slots={info.get('tier_slots')} "
                    f"retraces={info.get('retraces')} tiers={tiers}")
    run.notes["run_info"] = {
        k: info.get(k) for k in (
            "path", "strategy_resolved", "supersteps", "pad_ratio",
            "h2d_arg_bytes", "d2h_bytes", "retraces", "routing", "platform",
            "rounds", "relaxed_slots", "tier_slots",
        )
    }
    return state


def _search(run, state, root):
    """One search to host-resident results: (wall, {state: array}, info)."""
    mix = run.traffic
    program = state["program"](**{**mix["args"], mix["roots"]["param"]: root})
    t = time.perf_counter()
    with run.annotate("submit"):
        result = state["graph"].compute().program(program).submit()
    with run.annotate("fetch"):
        arrays = {k: np.asarray(result.states[k])
                  for k in mix["result_states"]}
    wall = time.perf_counter() - t
    info = result.run_info
    assert info["supersteps"] < mix["args"]["max_iterations"], (
        f"search from {root} ran {info['supersteps']} rounds: ended by "
        "max_iterations, not by its fixpoint")
    return wall, arrays, info


def measure(run, state):
    roots, results = state["roots"], state["results"]
    cut = run.window_opened + run.seconds
    # the traced stretch: whole searches from the second on, until
    # `traced_seconds` have passed, so the counts of rounds are exact
    tracing = contextlib.ExitStack()
    trace_due, traced_from = run.trace, None
    rounds_traced = relaxed_traced = i = 0
    while time.perf_counter() < cut:
        if trace_due and (i >= 1 or run.seconds < 2):
            tracing.enter_context(run.traced())
            trace_due, traced_from = False, time.perf_counter()
        root = roots[i % len(roots)]
        wall, arrays, info = _search(run, state, root)
        results.append((root, wall, arrays))
        i += 1
        if traced_from is not None:
            rounds_traced += info["supersteps"]
            relaxed_traced += info.get("relaxed_slots", 0)
            if time.perf_counter() - traced_from >= run.traffic["traced_seconds"]:
                tracing.close()
                traced_from = None
    tracing.close()
    if run.trace:
        run.counts["supersteps_traced"] = rounds_traced
        run.counts["relaxed_slots_traced"] = relaxed_traced
        # what the bytes function of the step's roofline share reads
        run.shapes.update(rounds_traced=rounds_traced,
                          relaxed_slots_traced=relaxed_traced)
    run.counts["requests"] = len(results)
    walls = [w for _, w, _ in results]
    run.say(f"{len(walls)} searches in the window, walls "
            f"{min(walls):.4f}..{max(walls):.4f}s")
    by_root = {}
    for root, wall, _ in results:
        by_root.setdefault(root, []).append(wall)
    per_root = {r: statistics.median(ws) for r, ws in by_root.items()}
    run.notes["per_root_median_s"] = {str(r): w for r, w in per_root.items()}
    run.notes["walls_s"] = walls
    return {"submit_p50_s": statistics.median(per_root.values())}


def check(run, state):
    """Every search of the window against the reference: distances bit for
    bit, parents by Graph500's validation; the reference distances are
    computed once per key."""
    reference = run.reference(run.traffic["reference"])
    by_reason, want, failed = {}, {}, 0
    for root, _, got in state["results"]:
        if root not in want:
            want[root] = reference.expect(
                state["data"], **{**run.traffic["args"], "seed_index": root})
        wrong = reference.disagreements(got, want[root])
        failed += bool(wrong)
        for reason in wrong:
            by_reason[reason] = by_reason.get(reason, 0) + 1
    run.say(f"{len(state['results'])} searches against reference "
            f"{run.traffic['reference']!r} over {len(want)} keys")
    return {"attempted": len(state["results"]), "failed": failed,
            "by_reason": by_reason}


def teardown(run, state):
    state["graph"].close()
