"""Traffic driver `gap-repeat`: `submit-loop`'s analyst, submitting one
program back to back on a snapshot adopted warm, on ONE labelling of the
structure. The generated edge list is adopted under the permutation drawn
from the configuration's `structure_seed`, not from --seed, so every seed
runs the same ids, hence the same work: for a program whose work depends
on the ids and not only on the degrees (GAP's CC by Afforest: which
neighbours the sample links, how many passes and jumps its forest takes,
which vertices its finish reads), --seed would otherwise pick the work.
--seed still names the run and its notes.

Parameters (the traffic file) and the reported `submit_p50_s` are
`submit-loop`'s, whose measure, check and teardown this driver runs; only
the data differ. `roots` must be null."""

from __future__ import annotations

from data import EdgeList, rmat_edges


def _loop(run):
    return run.catalog.driver("submit-loop")


def setup(run):
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta, programs
    from janusgraph_tpu.olap.csr import csr_from_edges

    cfg, mix = run.config, run.traffic
    if mix.get("roots"):
        raise ValueError("traffic driver gap-repeat takes no roots")
    loop = _loop(run)
    with run.span("generate"):
        data = EdgeList(*rmat_edges(
            run.scale, cfg["edge_factor"], cfg["structure_seed"],
            cfg["structure_seed"]))
    run.shapes = {"vertices": data.n, "edges": data.m}
    graph = open_graph({"storage.backend": cfg["backend"],
                        **run.graph_options})
    with run.span("snapshot"):
        csr = csr_from_edges(data.n, data.src, data.dst)
        delta.get_snapshot(graph).adopt(csr, graph.backend.mutation_epoch())
    run.say(f"adopted R-MAT scale {run.scale} under the structure's own "
            f"labelling: {data.n} vertices, {data.m} edges, digest "
            f"{data.digest()} (generate {run.spans['generate']:.1f}s, csr + "
            f"adopt {run.spans['snapshot']:.1f}s)")
    state = {
        "graph": graph, "data": data, "roots": [None], "results": [],
        "program": getattr(programs, mix["program"]),
    }
    with run.span("warmup"):
        for i in range(mix["warmup_submits"]):
            wall, _, info = loop._submit(run, state, None)
            if i == 0:
                run.spans["first_submit"] = wall
            run.say(f"warm-up submit {i + 1}: {wall:.3f}s "
                    f"path={info.get('path')} "
                    f"supersteps={info.get('supersteps')} "
                    f"link_passes={info.get('link_passes')} "
                    f"compress_jumps={info.get('compress_jumps')} "
                    f"finish_slots={info.get('finish_slots')} "
                    f"tiers={[(t['F_cap'], t['E_cap']) for t in info.get('tiers', [])]}")
    run.notes["run_info"] = {
        k: info.get(k) for k in (
            "path", "supersteps", "link_passes", "compress_jumps",
            "finish_vertices", "finish_slots", "closure_slots",
            "giant_share", "h2d_arg_bytes", "d2h_bytes", "retraces",
            "platform",
        )
    }
    return state


def measure(run, state):
    return _loop(run).measure(run, state)


def check(run, state):
    return _loop(run).check(run, state)


def teardown(run, state):
    _loop(run).teardown(run, state)
