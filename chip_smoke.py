#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call, in
ONE process (the server runs as threads of the process that holds the
chip), and checks every answer against a plain reference written here.
Data is generated from --seed; nothing is read from a cache of graphs.

  phase 0  device: platform, device_kind, count, compile cache, native lib
  phase 1  a store that loads data and a server that answers: Graph500
           R-MAT (a/b/c/d .57/.19/.19/.05, edge factor 16) bulk-loaded into
           the in-memory backend at --store-scale, then over HTTP through
           the driver: V().count(), a hub's out-degree, a pageRank() step,
           a connectedComponent() step, and a 2-hop out().out().dedup()
           .count() repeated until the spillover planner promotes it
  phase 2  analytics at a size the device notices: R-MAT at --scale adopted
           as a warm snapshot (the way a fleet replica is warmed), then
           PageRank / 4-hop BFS / connected components through
           graph.compute() with the default configuration
  phase 3  the executor's pack on the device against the CPU oracle's
           replay of the ELL tree, bitwise

The first phase that fails ends the run with a non-zero exit code; no
failure is caught and carried past. Without a TPU the script fails in
phase 0 and says which platform JAX found. --cpu-rehearsal runs the same
control flow on the CPU at tiny scales and marks every line of its output,
the last one included, so it cannot be taken for a result.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# ranks: the executor's float32 against this file's float64. Every rank is
# at least (1 - damping) / n, so a purely relative bound checks them all
# (an absolute term near 1/n would wave the small ones through).
RANK_RTOL = 1e-4
DAMPING = 0.85
PR_ITERS = 20
BFS_HOPS = 4
# the 2-hop query must be dear enough on the row path to be promoted
# (default computer.spillover-min-cost-ms is 25) and cheap enough to run
# there the three times promotion waits for
TWO_HOP_MAX_TRAVERSERS = 100_000
#: explicit budget for a cold computer step that overran the server's
#: default deadline (see `served_step`); server.deadline.max-ms clamps it
COLD_DEADLINE_MS = 600_000.0

_T0 = time.perf_counter()
_PREFIX = ""


def say(msg: str) -> None:
    print(f"{_PREFIX}[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


class SmokeFailure(AssertionError):
    """A check of this script did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"  ok: {what}")


# ----------------------------------------------------------------- references
# Plain numpy / scipy over the generated edge list, independent of the
# package's executors.

def ref_pagerank(n, src, dst):
    import numpy as np

    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    dangling = outdeg == 0
    inv = 1.0 / np.maximum(outdeg, 1.0)
    rank = np.full(n, 1.0 / n)
    for _ in range(PR_ITERS):
        agg = np.bincount(dst, weights=(rank * inv)[src], minlength=n)
        rank = (1.0 - DAMPING) / n + DAMPING * (
            agg + rank[dangling].sum() / n
        )
    return rank


def adjacency(n, src, dst):
    """scipy CSR of the edge list (duplicate edges summed; only the
    pattern is used)."""
    import numpy as np
    import scipy.sparse as sp

    return sp.csr_matrix(
        (np.ones(len(src), np.int32), (src, dst)), shape=(n, n)
    )


def ref_components(adj):
    """Per vertex, the smallest index in its weakly connected component."""
    import numpy as np
    from scipy.sparse.csgraph import connected_components

    n = adj.shape[0]
    ncomp, label = connected_components(
        adj, directed=True, connection="weak"
    )
    smallest = np.full(ncomp, n, dtype=np.int64)
    np.minimum.at(smallest, label, np.arange(n, dtype=np.int64))
    return smallest[label]


def ref_bfs(adj, seed):
    """Hop distance from `seed` along out-edges, inf beyond BFS_HOPS."""
    from scipy.sparse.csgraph import dijkstra

    return dijkstra(
        adj, directed=True, indices=seed, unweighted=True, limit=BFS_HOPS,
    )


def out_lists(n, src, dst):
    """(indptr, neighbours) of the out-adjacency, duplicates kept."""
    import numpy as np

    order = np.argsort(src, kind="stable")
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return indptr, dst[order]


def ref_two_hop_distinct(indptr, nbr, v):
    import numpy as np

    first = nbr[indptr[v]:indptr[v + 1]]
    second = [nbr[indptr[u]:indptr[u + 1]] for u in first]
    return int(len(np.unique(np.concatenate(second)))) if second else 0


def assert_ranks(got, want, what: str) -> None:
    import numpy as np

    got = np.asarray(got, np.float64)
    check(got.shape == want.shape and bool(np.isfinite(got).all()),
          f"{what}: finite ranks of shape {want.shape}")
    rel = float(np.max(np.abs(got - want) / want))
    check(rel <= RANK_RTOL,
          f"{what}: max relative error {rel:.2e} <= {RANK_RTOL:g} "
          "(float32 vs float64 reference)")


# -------------------------------------------------------------- run records

def assert_on_device(expect: dict, what: str) -> dict:
    """The newest executor run record: it must name the devices this
    process holds, and no route may have been given up on the way."""
    from janusgraph_tpu.observability import flight_recorder, registry

    info = registry.last_run("olap") or {}
    routing = registry.last_run("olap.routing") or {}
    got = {k: info.get(k) for k in ("platform", "device_kind", "device_count")}
    want_route = "sharded" if expect["device_count"] > 1 else "tpu"
    check(
        got == expect
        and routing.get("routed") == want_route
        and "fallback" not in routing
        and not flight_recorder.events("sharded_auto_fallback"),
        f"{what}: ran on {got}, routed {routing.get('routed')!r} "
        f"({routing.get('reason')}), "
        f"fallback={routing.get('fallback')!r}, no sharded_auto_fallback "
        "event",
    )
    return info


def observe_run(info: dict, what: str) -> None:
    """Print a run record's shape — observations, not metrics."""
    say(f"  observed {what}: path={info.get('path')} "
        f"strategy_resolved={info.get('strategy_resolved')} "
        f"supersteps={info.get('supersteps')} "
        f"first_dispatch_s={info.get('first_dispatch_s')} "
        f"h2d_bytes={info.get('h2d_arg_bytes')} "
        f"retraces={info.get('retraces')} "
        f"exchange={(info.get('exchange') or {}).get('mode')}")


def observe_memory(devices) -> list:
    """Per-device allocator stats where the backend keeps them."""
    rows = []
    for d in devices:
        stats = d.memory_stats() or {}
        rows.append(stats.get("bytes_in_use"))
        say(f"  observed device {d.id}: bytes_in_use="
            f"{stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    return rows


# ------------------------------------------------------------------ phase 0

def phase_device(rehearsal: bool) -> dict:
    import jax

    from janusgraph_tpu import native
    from janusgraph_tpu.observability import profiler
    from janusgraph_tpu.olap.device import (
        configure_compile_cache,
        describe_devices,
    )

    say("phase 0: device")
    cache_dir = configure_compile_cache()
    devices = jax.devices()
    dev = describe_devices(devices)
    say(f"  jax {jax.__version__} platform={dev['platform']} "
        f"device_kind={dev['device_kind']!r} count={dev['device_count']}")
    say(f"  compile cache: {cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    want = "cpu" if rehearsal else "tpu"
    if dev["platform"] != want:
        raise SmokeFailure(
            f"JAX found platform {dev['platform']!r} "
            f"({dev['device_kind']!r} x {dev['device_count']}), not "
            f"{want!r}; only --cpu-rehearsal runs without a TPU"
        )
    peaks = profiler.device_peaks(dev["device_kind"])  # raises if unlisted
    check(peaks["source"].startswith("table:"),
          f"peaks for {dev['device_kind']!r}: "
          f"{peaks['peak_bytes_per_s']:.3g} B/s, "
          f"{peaks['peak_flops']:.3g} FLOP/s ({peaks['source']})")
    status = native.load_status()
    check(status != "unavailable",
          f"native library {status} (graphcsr.cpp; numpy fallback not in use)")
    return dev


# ------------------------------------------------------------------ phase 1

def served_step(client, gremlin: str, what: str):
    """One computer-step request under the server's DEFAULT deadline. A
    cold step pays scan + pack + compile inside it; if that does not fit,
    the server has still finished the work, so say so and ask again with
    an explicit per-request deadline. The default is not changed."""
    from janusgraph_tpu.driver.client import RemoteError

    t = time.perf_counter()
    try:
        out = client.submit(gremlin)
    except RemoteError as e:
        if e.code != 504:
            raise
        say(f"  FINDING: cold {what} overran the server's default deadline "
            f"after {time.perf_counter() - t:.1f}s ({e}); asking again with "
            f"X-Deadline-Ms={COLD_DEADLINE_MS:.0f}")
        t = time.perf_counter()
        out = client.submit(gremlin, deadline_ms=COLD_DEADLINE_MS)
    say(f"  observed {what} request wall: {time.perf_counter() - t:.2f}s")
    return out


def phase_served(dev: dict, scale: int, seed: int, graph_cfg: dict) -> None:
    import numpy as np

    from janusgraph_tpu.cli import build_server
    from janusgraph_tpu.core.bulk import bulk_add_edges, bulk_add_vertices
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.driver.client import JanusGraphClient
    from janusgraph_tpu.observability import flight_recorder, registry
    from janusgraph_tpu.olap.generators import rmat_edges
    from janusgraph_tpu.server import JanusGraphManager

    say(f"phase 1: store + server, R-MAT scale {scale} edge factor 16")
    n, src, dst = rmat_edges(scale, 16, seed=seed)
    src, dst = src.astype(np.int64), dst.astype(np.int64)
    graph = open_graph({"storage.backend": "inmemory", **graph_cfg})
    server = None
    try:
        t = time.perf_counter()
        vids = bulk_add_vertices(graph, n)
        bulk_add_edges(graph, "link", vids[src], vids[dst])
        say(f"  observed load: {n} vertices, {len(src)} edges in "
            f"{time.perf_counter() - t:.1f}s (host-bound bulk loader)")
        index_of = {int(v): i for i, v in enumerate(vids)}

        manager = JanusGraphManager()
        manager.put_graph("graph", graph)
        server = build_server(graph, manager, "graph", "127.0.0.1", 0).start()
        # the socket outlasts every deadline the server may apply, so an
        # overrun comes back as the server's own structured 504
        client = JanusGraphClient(
            "127.0.0.1", server.port, http_timeout_s=900.0
        )

        check(client.submit("g.V().count()") == n, f"V().count() == {n}")
        outdeg = np.bincount(src, minlength=n)
        hub = int(np.argmax(outdeg))
        got = client.submit(f"g.V({int(vids[hub])}).out().count()")
        check(got == int(outdeg[hub]),
              f"hub out-degree == {int(outdeg[hub])}")

        # a sample that holds the extremes and a random spread
        want_rank = ref_pagerank(n, src, dst)
        rng = np.random.default_rng(seed)
        sample = np.unique(np.concatenate([
            np.argsort(-want_rank)[:16], rng.choice(n, 48, replace=False),
        ]))
        ids = ",".join(str(int(vids[i])) for i in sample)

        rows = served_step(
            client,
            f"g.V({ids}).pageRank().project('id','rank')"
            ".by(__.id()).by('pagerank')",
            "pageRank()",
        )
        got_rank = {index_of[r["id"]]: r["rank"] for r in rows}
        check(sorted(got_rank) == sample.tolist(),
              f"pageRank() answered for all {len(sample)} sampled vertices")
        assert_ranks([got_rank[i] for i in sample], want_rank[sample],
                     "pageRank() step")
        observe_run(assert_on_device(dev, "pageRank() step"), "pageRank()")

        want_comp = ref_components(adjacency(n, src, dst))
        # the step names a component by its smallest member's vertex id
        smallest_vid = np.full(n, np.iinfo(np.int64).max, dtype=np.int64)
        np.minimum.at(smallest_vid, want_comp, vids)
        rows = served_step(
            client,
            f"g.V({ids}).connectedComponent().project('id','component')"
            ".by(__.id()).by('component')",
            "connectedComponent()",
        )
        got_comp = {index_of[r["id"]]: r["component"] for r in rows}
        check(
            [got_comp.get(int(i)) for i in sample]
            == [int(smallest_vid[want_comp[i]]) for i in sample],
            f"connectedComponent() exact on {len(sample)} sampled vertices",
        )
        observe_run(
            assert_on_device(dev, "connectedComponent() step"),
            "connectedComponent()",
        )

        # the 2-hop query: the dearest start the row path can still walk
        indptr, nbr = out_lists(n, src, dst)
        traversers = np.bincount(
            src, weights=outdeg[dst].astype(np.float64), minlength=n
        )
        eligible = np.where(traversers <= TWO_HOP_MAX_TRAVERSERS)[0]
        start = int(eligible[np.argmax(traversers[eligible])])
        want_two = ref_two_hop_distinct(indptr, nbr, start)
        query = f"g.V({int(vids[start])}).out().out().dedup().count()"
        spilled_before = registry.get_count("olap.spillover.spilled")
        for attempt in range(1, 9):
            t = time.perf_counter()
            got = client.submit(query)
            wall = time.perf_counter() - t
            spilled = (
                registry.get_count("olap.spillover.spilled") > spilled_before
            )
            check(got == want_two,
                  f"2-hop distinct count == {want_two} (attempt {attempt}, "
                  f"{'spilled' if spilled else 'row path'}, {wall:.2f}s, "
                  f"{int(traversers[start])} traversers)")
            if spilled:
                break
        check(spilled, "olap.spillover.spilled moved: the planner promoted "
                       "the 2-hop shape under its configured thresholds")
        errors = [
            e for e in flight_recorder.events("spillover_fallback")
            if str(e.get("reason", "")).startswith("error:")
        ]
        check(not errors, f"no 'error:' spillover fallback ({errors[:1]})")
        block = (registry.last_run("olap.spillover") or {}).get("spillover")
        check(block and block.get("fallback") is None,
              f"spilled run record: {block}")
        info = registry.last_run("olap") or {}
        check(info.get("platform") == dev["platform"],
              f"spilled supersteps ran on platform {info.get('platform')!r}")
    finally:
        if server is not None:
            server.stop()
        graph.close()


# ------------------------------------------------------------------ phase 2

def phase_analytics(dev: dict, scale: int, seed: int, devices) -> None:
    import numpy as np

    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta
    from janusgraph_tpu.olap.generators import rmat_edges
    from janusgraph_tpu.olap.csr import csr_from_edges
    from janusgraph_tpu.olap.programs import (
        ConnectedComponentsProgram,
        PageRankProgram,
        ShortestPathProgram,
    )
    from janusgraph_tpu.olap.programs.shortest_path import INF

    say(f"phase 2: analytics, R-MAT scale {scale} edge factor 16")
    t = time.perf_counter()
    n, src32, dst32 = rmat_edges(scale, 16, seed=seed)
    csr = csr_from_edges(n, src32, dst32)
    src, dst = src32.astype(np.int64), dst32.astype(np.int64)
    say(f"  observed generate + CSR: {n} vertices, {csr.num_edges} edges in "
        f"{time.perf_counter() - t:.1f}s")

    graph = open_graph({"storage.backend": "inmemory"})
    try:
        # the way a fleet replica is warmed (server/fleet.warm_replica):
        # the snapshot adopts a ready CSR, and submits read no store
        t = time.perf_counter()
        delta.get_snapshot(graph).adopt(csr, graph.backend.mutation_epoch())
        say(f"  observed snapshot adopt: {time.perf_counter() - t:.3f}s")

        def submit(program, what):
            """Twice through graph.compute(): the first submit packs,
            ships and compiles; the second meets the snapshot's cached
            executor. The answer checked is the warm one."""
            t = time.perf_counter()
            cold = graph.compute().program(program).submit()
            t_cold = time.perf_counter() - t
            observe_run(assert_on_device(dev, f"{what} (cold)"), what)
            t = time.perf_counter()
            result = graph.compute().program(program).submit()
            say(f"  observed {what}: first submit (pack + compile) "
                f"{t_cold:.2f}s, warm submit {time.perf_counter() - t:.3f}s")
            assert_on_device(dev, f"{what} (warm)")
            check(not any("fallback" in r.run_info["routing"]
                          for r in (cold, result)),
                  f"{what}: no fallback in either result's run_info routing")
            return result

        res = submit(PageRankProgram(max_iterations=PR_ITERS, tol=0.0),
                     "PageRank")
        in_use = observe_memory(devices)
        if len(devices) > 1 and dev["platform"] == "tpu":
            # the CPU backend keeps no allocator stats to compare
            check(all(in_use) and max(in_use) <= 2 * min(in_use),
                  f"per-device bytes_in_use within 2x: {in_use}")
        assert_ranks(res.states["rank"], ref_pagerank(n, src, dst),
                     "PageRank")

        adj = adjacency(n, src, dst)
        hub = int(np.argmax(np.bincount(src, minlength=n)))
        res = submit(
            ShortestPathProgram(seed_index=hub, max_iterations=BFS_HOPS),
            f"{BFS_HOPS}-hop BFS",
        )
        got = np.asarray(res.states["distance"])  # float32; INF = unreached
        got = np.where(got >= INF, np.inf, got.astype(np.float64))
        want = ref_bfs(adj, hub)
        check(np.array_equal(got, want),
              f"BFS distances exact; {int(np.isfinite(want).sum())} vertices "
              f"within {BFS_HOPS} hops of the hub")

        res = submit(ConnectedComponentsProgram(), "connected components")
        got = np.asarray(res.states["component"]).astype(np.int64)
        want = ref_components(adj)
        check(np.array_equal(got, want),
              f"components exact; {len(np.unique(want))} components")
        observe_memory(devices)
    finally:
        graph.close()


# ------------------------------------------------------------------ phase 3

def phase_kernels(dev: dict, scale: int, seed: int) -> None:
    import numpy as np

    from janusgraph_tpu.olap.cpu_executor import CPUExecutor
    from janusgraph_tpu.olap.generators import rmat_csr
    from janusgraph_tpu.olap.kernels import ELLPack, ell_aggregate
    from janusgraph_tpu.olap.programs import PageRankProgram
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor
    from janusgraph_tpu.olap.vertex_program import Combiner, VertexProgram

    say(f"phase 3: the executor's pack against the ELL replay at scale "
        f"{scale}")
    csr = rmat_csr(scale, 16, seed)
    n = csr.num_vertices

    class Echo(VertexProgram):
        """One superstep: every vertex sends x and keeps what it folded."""

        max_iterations = 1

        def __init__(self, x):
            self.x = x

        def setup(self, graph, xp):
            return {"x": xp.asarray(self.x)}, {}

        def message(self, state, superstep, graph, xp):
            return state["x"]

        def apply(self, state, aggregated, superstep, memory_in, graph, xp):
            return {"x": aggregated}, {}

        def terminate(self, memory):
            return False

    x = np.random.default_rng(seed).uniform(-1.0, 1.0, n).astype(np.float32)
    ex = TPUExecutor(csr)
    t = time.perf_counter()
    got = np.asarray(ex.run(Echo(x))["x"])
    say(f"  observed: one superstep, first run (pack + compile) "
        f"{time.perf_counter() - t:.2f}s")
    info = ex.last_run_info
    check(info["strategy_resolved"] == "hybrid"
          and info["platform"] == dev["platform"],
          f"the dense superstep ran on the {info['strategy_resolved']!r} "
          f"pack ({info['pad_ratio']} slots an edge) on {info['platform']!r}")
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(csr.in_indptr))
    want = ell_aggregate(
        np, ELLPack(csr.in_src.astype(np.int64), dst, None, n), x,
        Combiner.SUM,
    )
    check(np.array_equal(got, want),
          "the device's float32 sums bitwise-equal to the numpy replay of "
          "the ELL tree (the same reduction tree)")

    program = PageRankProgram(max_iterations=PR_ITERS, tol=0.0)
    got = np.asarray(ex.run(program)["rank"], np.float64)
    ell = np.asarray(
        CPUExecutor(csr, strategy="ell").run(program)["rank"], np.float64)
    rel = float(np.max(np.abs(got - ell) / ell))
    check(rel <= RANK_RTOL,
          f"PageRank against the CPU oracle's ELL replay: max relative "
          f"difference {rel:.2e} <= {RANK_RTOL:g} (the oracle keeps its "
          "state in float64)")


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    global _PREFIX

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--scale", type=int, default=20,
                    help="phase 2 R-MAT scale (default 20; 23 is the "
                         "north star's size)")
    ap.add_argument("--store-scale", type=int, default=18,
                    help="phase 1 R-MAT scale through the store (default "
                         "18: the host-bound bulk loader sets it)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the control flow on the CPU at tiny scales; "
                         "every output line is marked, none is a result")
    args = ap.parse_args(argv)

    graph_cfg = {}
    kernel_scale = 16
    if args.cpu_rehearsal:
        _PREFIX = "[cpu-rehearsal] "
        # before jax is imported: the rehearsal never takes a chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.store_scale, args.scale, kernel_scale = 6, 7, 6
        # at this size the row path never costs the default 25 ms
        graph_cfg = {"computer.spillover-min-cost-ms": 0.0}
        say("REHEARSAL on the CPU at tiny scales: checks control flow "
            "only, no line below is a device result")

    import jax

    dev = phase_device(args.cpu_rehearsal)
    phase_served(dev, args.store_scale, args.seed, graph_cfg)
    phase_analytics(dev, args.scale, args.seed, jax.devices())
    phase_kernels(dev, kernel_scale, args.seed)

    say(f"all phases passed in {time.perf_counter() - _T0:.1f}s")
    print(_PREFIX + json.dumps({
        "ok": True,
        "device": {
            "platform": dev["platform"],
            "kind": dev["device_kind"],
            "count": dev["device_count"],
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
