"""Traffic driver `closed-loop-http`: `clients` threads, each with a
`JanusGraphClient` of its own, each sending its next Gremlin request only
when the reply to its last has come (after `think_time_s`), so at most
`clients` requests are ever outstanding. The graph is bulk-loaded through
the store and served over HTTP by the in-process server (threads of this
process: a chip belongs to one process), every option at its default.

Parameters (the traffic file): `clients`, `think_time_s`, `pool` (`draw`:
`edge-source`, the source end of random edges, so degree-proportional, or
`uniform`; `size`; the same vertices of the structure for every seed, under
that seed's ids, each client walking them in its own seeded order),
`templates` (each: `name`, `gremlin` with `<id>`
placeholders, `weight`, `reference`, optional `max_traversers`, which keeps
a start vertex only where the traversers after the template's `.out()`
hops number at most that, and `promote`: the shape must be promoted to the
device by the spillover planner before the window), `warmup_requests`,
`traced_seconds`.

Measures `request_p50_ms` and `request_p95_ms` over every request that
started inside the window, timed around `client.submit()`; the manifest
says which of them is an end-to-end metric."""

from __future__ import annotations

import threading
import time

import numpy as np

from data import EdgeList, rmat_edges

#: the planner promotes a shape above its cost threshold after 3 sightings
MAX_PROMOTION_ATTEMPTS = 8


def setup(run):
    from janusgraph_tpu.cli import build_server
    from janusgraph_tpu.core.bulk import bulk_add_edges, bulk_add_vertices
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.driver.client import JanusGraphClient
    from janusgraph_tpu.server import JanusGraphManager

    cfg, mix = run.config, run.traffic
    with run.span("generate"):
        data = EdgeList(*rmat_edges(
            run.scale, cfg["edge_factor"], cfg["structure_seed"], run.seed))
    run.shapes = {"vertices": data.n, "edges": data.m}
    graph = open_graph({"storage.backend": cfg["backend"],
                        **run.graph_options})
    state = {"graph": graph, "data": data, "server": None, "records": []}
    try:
        with run.span("load"):
            vids = bulk_add_vertices(graph, data.n)
            bulk_add_edges(
                graph, cfg["edge_label"],
                vids[data.src.astype(np.int64)],
                vids[data.dst.astype(np.int64)],
            )
        manager = JanusGraphManager()
        manager.put_graph("graph", graph)
        server = build_server(graph, manager, "graph", "127.0.0.1", 0).start()
        state.update(server=server, vids=vids)
        run.say(f"loaded R-MAT scale {run.scale}: {data.n} vertices, "
                f"{data.m} edges, digest {data.digest()} (generate "
                f"{run.spans['generate']:.1f}s, bulk load "
                f"{run.spans['load']:.1f}s); server on port {server.port}")
        state["pool"], traversers = _draw_pool(run, data)
        client = JanusGraphClient("127.0.0.1", server.port)
        with run.span("warmup"):
            _promote(run, state, client, traversers)
            for i in range(mix["warmup_requests"]):
                template = mix["templates"][i % len(mix["templates"])]
                _request(run, state, client, template,
                         i % len(state["pool"]), state["records"])
    except BaseException:
        teardown(run, state)
        raise
    state["warmup_records"] = len(state["records"])
    return state


def _draw_pool(run, data):
    """The pool of start vertices (indices into the edge list's id space)
    and, per template name, every vertex's traverser count."""
    mix = run.traffic
    # drawn from the structure (edges keep the generator's order under
    # every labelling), so every seed asks about the same vertices
    rng = np.random.default_rng([run.config["structure_seed"], 2])
    size = mix["pool"]["size"]
    if mix["pool"]["draw"] == "edge-source":
        drawn = data.src[rng.integers(0, data.m, size * 16)]
    else:
        drawn = data.perm[rng.integers(0, data.n, size * 16)]
    keep = np.ones(len(drawn), bool)
    traversers = {}
    for template in mix["templates"]:
        count = np.ones(data.n, np.float64)
        for _ in range(template["gremlin"].count(".out()")):
            # traversers after one more hop: each out-edge carries its
            # target's count back to the source
            count = np.bincount(
                data.src, weights=count[data.dst], minlength=data.n
            )
        traversers[template["name"]] = count
        if "max_traversers" in template:
            keep &= count[drawn] <= template["max_traversers"]
    pool = drawn[keep][:size].astype(np.int64)
    if len(pool) < size:
        raise RuntimeError(f"only {len(pool)} of {size} eligible starts")
    run.notes["eligible_share"] = float(keep.mean())
    run.say(f"start vertices: pool of {size}, {100 * keep.mean():.1f}% of "
            f"{mix['pool']['draw']} draws eligible")
    return pool, traversers


def _request(run, state, client, template, pool_index, records):
    """One request, appended to `records`: (template, pool index, start,
    wall, answer, error). Never raises: a failure is a record."""
    vid = int(state["vids"][state["pool"][pool_index]])
    query = template["gremlin"].replace("<id>", str(vid))
    got = err = None
    t = time.perf_counter()
    try:
        with run.annotate("request"):
            got = client.submit(query)
    except Exception as e:  # noqa: BLE001 - any failure is counted by reason
        err = f"{type(e).__name__}:{getattr(e, 'code', '')}"
    wall = time.perf_counter() - t
    record = (template["name"], pool_index, t, wall, got, err)
    records.append(record)
    return record


def _promote(run, state, client, traversers):
    """Send each `promote` template from the pool's dearest start until the
    planner, under its own thresholds, moves it to the device (cheap starts
    first would keep the shape's mean cost under the threshold)."""
    from janusgraph_tpu.observability import registry

    for template in run.traffic["templates"]:
        if not template.get("promote"):
            continue
        cost = traversers[template["name"]][state["pool"]]
        dearest = int(np.argmax(cost))
        before = registry.get_count("olap.spillover.spilled")
        for attempt in range(1, MAX_PROMOTION_ATTEMPTS + 1):
            *_, wall, _, err = _request(
                run, state, client, template, dearest, state["records"])
            spilled = registry.get_count("olap.spillover.spilled") > before
            run.say(f"promotion attempt {attempt} of {template['name']} "
                    f"({int(cost[dearest])} traversers): {wall:.2f}s, "
                    f"{'spilled' if spilled else 'row path'} {err or ''}")
            if spilled:
                # the first spilled request scans, packs, ships and loads
                run.spans["snapshot"] = run.spans.get("snapshot", 0.0) + wall
                record = registry.last_run("olap.spillover") or {}
                run.notes["run_info"] = {
                    "path": record.get("executor"),
                    "supersteps": record.get("supersteps"),
                    "spillover": record.get("spillover"),
                }
                break
        else:
            raise RuntimeError(
                f"{template['name']} was not promoted in "
                f"{MAX_PROMOTION_ATTEMPTS} requests"
            )


def measure(run, state):
    from janusgraph_tpu.driver.client import JanusGraphClient

    mix = run.traffic
    templates = mix["templates"]
    weights = np.array([t["weight"] for t in templates], np.float64)
    cut = run.window_opened + run.seconds
    lock = threading.Lock()
    outstanding = {"now": 0, "most": 0}
    per_client = [[] for _ in range(mix["clients"])]

    def client_loop(k):
        client = JanusGraphClient("127.0.0.1", state["server"].port)
        rng = np.random.default_rng([run.seed, 3, k])
        order = rng.permutation(len(state["pool"]))
        picks = rng.choice(len(templates), 4096, p=weights / weights.sum())
        i = 0
        while time.perf_counter() < cut:
            with lock:
                outstanding["now"] += 1
                outstanding["most"] = max(outstanding["most"],
                                          outstanding["now"])
            _request(run, state, client, templates[picks[i % len(picks)]],
                     int(order[i % len(order)]), per_client[k])
            with lock:
                outstanding["now"] -= 1
            i += 1
            if mix["think_time_s"]:
                time.sleep(mix["think_time_s"])

    threads = [
        threading.Thread(target=client_loop, args=(k,), name=f"client-{k}")
        for k in range(mix["clients"])
    ]
    for th in threads:
        th.start()
    if run.trace:
        time.sleep(min(1.0, run.seconds / 4))
        with run.traced():
            time.sleep(min(mix["traced_seconds"], run.seconds / 2))
    for th in threads:
        th.join()
    drained = time.perf_counter() - cut
    records = [r for recs in per_client for r in recs]
    state["records"].extend(records)
    walls_ms = np.array([1000.0 * r[3] for r in records])
    run.counts["requests"] = len(records)
    run.counts["max_outstanding"] = outstanding["most"]
    run.notes["drain_s"] = drained
    run.notes["walls_ms"] = walls_ms.tolist()
    run.say(f"{len(records)} requests in the window from {mix['clients']} "
            f"clients ({len(records) / run.seconds:.1f}/s), at most "
            f"{outstanding['most']} outstanding, walls "
            f"{walls_ms.min():.2f}..{walls_ms.max():.2f} ms, drained "
            f"{drained:.3f}s past the cut")
    return {
        "request_p50_ms": float(np.percentile(walls_ms, 50)),
        "request_p95_ms": float(np.percentile(walls_ms, 95)),
    }


def check(run, state):
    """Every answer of the window (and of the warm-up) against its
    template's reference; an exception or an HTTP error is a failure too.
    `attempted` and `failed` count the window's requests."""
    references = {
        t["name"]: run.reference(t["reference"])
        for t in run.traffic["templates"]
    }
    want, by_reason, attempted = {}, {}, 0
    for n, (name, pool_index, _, _, got, err) in enumerate(state["records"]):
        in_window = n >= state["warmup_records"]
        attempted += in_window
        key = (name, pool_index)
        if key not in want:
            want[key] = references[name].expect(
                state["data"], index=int(state["pool"][pool_index])
            )
        reason = None
        if err is not None:
            reason = f"error:{err}"
        elif not references[name].agrees(got, want[key]):
            reason = "wrong-answer"
        if reason and not in_window:
            raise RuntimeError(f"warm-up request {key} failed: {reason}")
        if reason:
            by_reason[reason] = by_reason.get(reason, 0) + 1
    run.say(f"{attempted} answers against their references over "
            f"{len(want)} distinct requests")
    return {"attempted": attempted, "failed": sum(by_reason.values()),
            "by_reason": by_reason}


def teardown(run, state):
    if state.get("server") is not None:
        state["server"].stop()
    state["graph"].close()
