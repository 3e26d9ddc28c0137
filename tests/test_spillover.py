"""OLTP->OLAP spillover (ISSUE 12): hot multi-hop traversal shapes
compile to frontier supersteps over a cached CSR snapshot, set-equal to
the step-by-step walk — including mid-transaction (tx-overlay
reconciliation), under brownout (transparent fallback), and across
snapshot staleness (refresh within the bound, refusal beyond it).

Oracle everywhere: the SAME traversal with the planner disabled (the
row-by-row walk). The digest table is process-global, so every test
resets it and uses its own graph/planner.
"""

import collections
import os
import queue
import threading
import time

import pytest

from janusgraph_tpu.core.graph import open_graph
from janusgraph_tpu.observability import flight_recorder, registry
from janusgraph_tpu.observability.profiler import digest_table
from janusgraph_tpu.olap import spillover as sp


SPILL_CFG = {
    "schema.default": "auto",
    "computer.spillover": True,
    # promote on the FIRST observation so tests teach a shape with one
    # row-wise run and spill from the second on
    "computer.spillover-min-cost-ms": 0.0,
    "computer.spillover-min-seen": 1,
    "computer.sharded-auto": False,
}


def _social_graph(extra_cfg=None, n_people=12, n_places=3):
    g = open_graph({**SPILL_CFG, **(extra_cfg or {})})
    tx = g.new_transaction()
    people = [tx.add_vertex("person", name=f"p{i}") for i in range(n_people)]
    places = [tx.add_vertex("place", name=f"c{i}") for i in range(n_places)]
    import random

    rng = random.Random(11)
    for i, v in enumerate(people):
        for j in rng.sample(range(n_people), 4):
            tx.add_edge(v, "knows", people[j])
        tx.add_edge(v, "lives", places[i % n_places])
    # a self-loop and a parallel edge: multiplicity edge cases the count
    # vector must reproduce exactly
    tx.add_edge(people[0], "knows", people[0])
    tx.add_edge(people[1], "knows", people[2])
    tx.add_edge(people[1], "knows", people[2])
    tx.commit()
    return g, [v.id for v in people], [v.id for v in places]


def _spill_count(counter="olap.spillover.spilled"):
    return registry.snapshot().get(counter, {}).get("count", 0)


def _ab(g, build, as_count=False):
    """(row result, spilled result, engaged): run once to teach the
    digest table, then A/B the spilled run against the disabled-planner
    walk. List results compare as sorted lists (set/multiset equality is
    the contract; order is not)."""
    planner = g.spillover_planner
    planner.enabled = True
    run = (lambda t: t.count()) if as_count else (lambda t: t.to_list())
    run(build())  # teach
    before = _spill_count()
    spilled = run(build())
    engaged = _spill_count() > before
    planner.enabled = False
    try:
        row = run(build())
    finally:
        planner.enabled = True
    if not as_count:
        row, spilled = sorted(map(repr, row)), sorted(map(repr, spilled))
    return row, spilled, engaged


@pytest.fixture(autouse=True)
def _fresh_tables():
    digest_table.reset()
    yield
    digest_table.reset()


# ----------------------------------------------------------- set equality
@pytest.mark.parametrize("chain", [
    lambda t: t.V().out("knows").out("knows"),
    lambda t: t.V().out("knows").out("knows").out("knows"),
    lambda t: t.V().in_("knows").in_("knows"),
    lambda t: t.V().both("knows").both("knows"),
    lambda t: t.V().out().out(),
    lambda t: t.V().out("knows").out("lives"),
    lambda t: t.V().out("knows").out("knows").dedup(),
    lambda t: t.V().out("knows").out("knows").id_(),
    lambda t: t.V().out("knows").out("knows").dedup().id_(),
    lambda t: t.V().has_label("person").out("knows").out("knows"),
    lambda t: t.V().out("knows").has_label("person").out("lives"),
])
def test_spilled_results_set_equal(chain):
    g, _people, _places = _social_graph()
    try:
        row, spilled, engaged = _ab(g, lambda: chain(g.traversal()))
        assert engaged, "spillover did not engage on a promoted shape"
        assert row == spilled
    finally:
        g.close()


def test_spilled_count_terminal_and_count_step(extra=None):
    g, people, _places = _social_graph()
    try:
        row, spilled, engaged = _ab(
            g,
            lambda: g.traversal().V().out("knows").out("knows"),
            as_count=True,
        )
        assert engaged and row == spilled
        # count as a STEP: spilled chain yields one int traverser
        row2, spilled2, engaged2 = _ab(
            g,
            lambda: g.traversal().V().out("knows").out("knows").count_(),
        )
        assert engaged2 and row2 == spilled2
        # seeded start with DUPLICATE ids: seed multiplicity preserved
        row3, spilled3, engaged3 = _ab(
            g,
            lambda: g.traversal().V(
                people[0], people[0], people[1]
            ).out("knows").out("knows"),
            as_count=True,
        )
        assert engaged3 and row3 == spilled3
        # trailing edge expansion with a count terminal
        row4, spilled4, engaged4 = _ab(
            g,
            lambda: g.traversal().V().out("knows").out_e("knows"),
            as_count=True,
        )
        assert engaged4 and row4 == spilled4
    finally:
        g.close()


# -------------------------------------------------- tx-overlay read-your-writes
def test_overlay_uncommitted_adds_and_deletes():
    """The acceptance case: the SAME transaction holds uncommitted adds
    AND deletes on the traversed edges — the spilled result must be
    read-your-writes set-equal to the row walk."""
    from janusgraph_tpu.core.codecs import Direction
    from janusgraph_tpu.core.traversal import GraphTraversalSource

    g, people, _places = _social_graph()
    try:
        # teach + promote the shape on a clean tx first
        _ab(g, lambda: g.traversal().V().out("knows").out("knows"))
        tx = g.new_transaction()
        v0 = tx.get_vertex(people[0])
        v1 = tx.get_vertex(people[1])
        # uncommitted adds: a brand-new vertex wired into the traversed
        # label, plus a fresh edge between committed vertices
        nv = tx.add_vertex("person", name="fresh")
        tx.add_edge(v0, "knows", nv)
        tx.add_edge(nv, "knows", v1)
        tx.add_edge(v1, "knows", v0)
        # uncommitted deletes: one committed edge instance (parallel
        # edges stay count-correct), and a whole vertex
        es = tx.get_edges(v1, Direction.OUT, ("knows",))
        tx.remove_edge(es[0])
        tx.remove_vertex(tx.get_vertex(people[11]))

        def build():
            return GraphTraversalSource(g, tx).V().out("knows").out("knows")

        planner = g.spillover_planner
        before = _spill_count()
        spilled = build().count()
        assert _spill_count() > before, "overlay run did not spill"
        planner.enabled = False
        try:
            row = build().count()
        finally:
            planner.enabled = True
        assert spilled == row
        # the run record carries the overlay block
        info = registry.last_run("olap.spillover")
        block = info["spillover"]
        assert block["fallback"] is None
        assert block["overlay"]["added"] == 3
        assert block["overlay"]["new_vertices"] == 1
        assert block["overlay"]["removed"] == 1
        assert block["overlay"]["deleted"] >= 1
        # dedup'd endpoints too, not just totals
        before = _spill_count()
        spilled_ids = sorted(build().dedup().id_().to_list())
        planner.enabled = False
        try:
            row_ids = sorted(build().dedup().id_().to_list())
        finally:
            planner.enabled = True
        assert spilled_ids == row_ids
    finally:
        g.close()


def test_overlay_overflow_falls_back():
    g, people, _places = _social_graph(
        {"computer.spillover-max-overlay": 2}
    )
    try:
        _ab(g, lambda: g.traversal().V().out("knows").out("knows"))
        from janusgraph_tpu.core.traversal import GraphTraversalSource

        tx = g.new_transaction()
        v0 = tx.get_vertex(people[0])
        for i in range(4):
            tx.add_edge(v0, "knows", tx.get_vertex(people[i + 1]))
        before = _spill_count()
        c = GraphTraversalSource(g, tx).V().out("knows").out("knows").count()
        assert _spill_count() == before, "overflowed overlay still spilled"
        g.spillover_planner.enabled = False
        try:
            row = GraphTraversalSource(g, tx).V().out(
                "knows"
            ).out("knows").count()
        finally:
            g.spillover_planner.enabled = True
        assert c == row
        events = flight_recorder.events("spillover_fallback")
        assert any(
            e.get("reason") == "overlay-overflow" for e in events
        )
    finally:
        g.close()


# ----------------------------------------------------------- fallback paths
def test_unsupported_step_falls_back_transparently():
    """A promoted digest whose chain carries an unsupported step runs
    row-by-row with a spillover_fallback flight event and zero errors."""
    g, _people, _places = _social_graph()
    try:
        def build():
            return g.traversal().V().out("knows").out("knows").values("name")

        build().to_list()  # teach: digest observed once
        # force-promote the digest so the refusal is event-worthy
        shape, digest = sp.traversal_digest(build())
        planner = g.spillover_planner
        with planner._lock:
            assert planner._check_promotion(digest, shape)
        before = flight_recorder.counts().get("spillover_fallback", 0)
        spilled_view = build().to_list()
        planner.enabled = False
        try:
            row_view = build().to_list()
        finally:
            planner.enabled = True
        assert sorted(spilled_view) == sorted(row_view)
        events = flight_recorder.events("spillover_fallback")
        assert flight_recorder.counts()["spillover_fallback"] > before
        assert any(
            e["digest"] == digest
            and str(e.get("reason", "")).startswith("unsupported:")
            for e in events
        )
    finally:
        g.close()


def test_rung2_brownout_falls_back_transparently():
    """Brownout rung 2 refuses OLAP submits — the spilled path must fall
    back to the row walk (same results, flight event, zero errors)."""
    from janusgraph_tpu.server import admission as adm

    g, _people, _places = _social_graph()
    try:
        def build():
            return g.traversal().V().out("knows").out("knows")

        row, spilled, engaged = _ab(g, build)
        assert engaged and row == spilled
        ctl = adm.AdmissionController()
        ctl.brownout.rung = adm.RUNG_REFUSE_OLAP
        adm.set_active(ctl)
        try:
            before = _spill_count()
            browned = build().to_list()
            assert _spill_count() == before, "spilled during rung-2 brownout"
            g.spillover_planner.enabled = False
            try:
                row2 = build().to_list()
            finally:
                g.spillover_planner.enabled = True
            assert sorted(map(repr, browned)) == sorted(map(repr, row2))
            assert any(
                e.get("reason") == "brownout"
                for e in flight_recorder.events("spillover_fallback")
            )
        finally:
            adm.set_active(None)
        # ladder cleared: the next run spills again
        before = _spill_count()
        build().to_list()
        assert _spill_count() > before
    finally:
        g.close()


def test_staleness_guard_refuses_then_repacks():
    g, people, _places = _social_graph(
        {"computer.spillover-max-staleness": 0}
    )
    try:
        def build():
            return g.traversal().V().out("knows").out("knows")

        row, spilled, engaged = _ab(g, build, as_count=True)
        assert engaged and row == spilled
        # a committed write from ANOTHER tx after the pack
        tx = g.new_transaction()
        tx.add_edge(
            tx.get_vertex(people[0]), "knows", tx.get_vertex(people[5])
        )
        tx.commit()
        stale_before = registry.snapshot().get(
            "olap.spillover.stale", {}
        ).get("count", 0)
        c1 = build().count()  # falls back: snapshot beyond the bound
        assert registry.snapshot()["olap.spillover.stale"]["count"] == (
            stale_before + 1
        )
        packs_before = registry.snapshot()["olap.spillover.packs"]["count"]
        before = _spill_count()
        c2 = build().count()  # repacked: spills again, fresh snapshot
        assert _spill_count() > before
        assert registry.snapshot()["olap.spillover.packs"]["count"] == (
            packs_before + 1
        )
        g.spillover_planner.enabled = False
        try:
            row2 = build().count()
        finally:
            g.spillover_planner.enabled = True
        assert c1 == c2 == row2
    finally:
        g.close()


def test_refresh_within_staleness_bound():
    g, people, _places = _social_graph(
        {"computer.spillover-max-staleness": 10_000}
    )
    try:
        def build():
            return g.traversal().V().out("knows").out("knows")

        _ab(g, build, as_count=True)
        tx = g.new_transaction()
        tx.add_edge(
            tx.get_vertex(people[2]), "knows", tx.get_vertex(people[3])
        )
        tx.commit()
        before = _spill_count()
        c = build().count()
        assert _spill_count() > before
        assert registry.snapshot()[
            "olap.spillover.refreshes"
        ]["count"] >= 1
        g.spillover_planner.enabled = False
        try:
            row = build().count()
        finally:
            g.spillover_planner.enabled = True
        assert c == row
    finally:
        g.close()


# -------------------------------------------------------------- promotion
def test_promotion_policy_thresholds():
    g, _people, _places = _social_graph({
        "computer.spillover-min-seen": 3,
        "computer.spillover-min-cost-ms": 0.0,
    })
    try:
        def build():
            return g.traversal().V().out("knows").out("knows")

        base = _spill_count()
        for _ in range(2):
            build().to_list()
        assert _spill_count() == base, "promoted below min-seen"
        build().to_list()  # 3rd observation crosses min-seen
        before = _spill_count()
        build().to_list()
        assert _spill_count() > before
        shape, digest = sp.traversal_digest(build())
        assert digest in sp.promoted_digests()
        snap = g.spillover_planner.promotion_snapshot()
        assert digest in snap and snap[digest]["spilled"] >= 1
    finally:
        g.close()


def test_min_cost_gate_keeps_cheap_shapes_on_row_path():
    g, _people, _places = _social_graph({
        "computer.spillover-min-cost-ms": 1e9,
        "computer.spillover-min-seen": 1,
    })
    try:
        base = _spill_count()
        for _ in range(3):
            g.traversal().V().out("knows").out("knows").to_list()
        assert _spill_count() == base
    finally:
        g.close()


def test_single_hop_never_considered():
    g, _people, _places = _social_graph()
    try:
        base = _spill_count()
        for _ in range(3):
            g.traversal().V().out("knows").to_list()
        assert _spill_count() == base
    finally:
        g.close()


# ---------------------------------------------------------- observability
def test_healthz_spillover_block_and_profile_marking():
    from janusgraph_tpu.server.server import healthz_snapshot

    g, _people, _places = _social_graph()
    try:
        row, spilled, engaged = _ab(
            g, lambda: g.traversal().V().out("knows").out("knows")
        )
        assert engaged
        block = healthz_snapshot()["spillover"]
        assert block["spilled"] >= 1
        assert block["packs"] >= 1
        assert block["promotions"] >= 1
        assert block["promoted_digests"], "promoted census empty"
        # GET /profile marks promoted digests — same data source
        promoted = sp.promoted_digests()
        assert set(block["promoted_digests"]) <= promoted
        info = registry.last_run("olap.spillover")
        assert info["spillover"]["digest"] in promoted
        assert info["spillover"]["hops"] == 2
        assert info["spillover"]["wall_ms"] > 0
    finally:
        g.close()


def test_profile_endpoint_marks_promoted_digests():
    """End to end over HTTP: /profile rows carry the promoted flag."""
    import json
    import urllib.request

    from janusgraph_tpu.server.manager import JanusGraphManager
    from janusgraph_tpu.server.server import JanusGraphServer

    g, _people, _places = _social_graph()
    mgr = JanusGraphManager()
    mgr.put_graph("graph", g)
    server = JanusGraphServer(manager=mgr, admission_enabled=False).start()
    try:
        _ab(g, lambda: g.traversal().V().out("knows").out("knows"))
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/profile"
        ) as r:
            payload = json.loads(r.read())
        marked = {
            d["digest"]: d["promoted"] for d in payload["digests"]
        }
        assert any(marked.values()), f"no promoted digest in {marked}"
    finally:
        server.stop()
        g.close()


# ------------------------------------------------------------- price book
def test_price_book_persists_across_graph_reopen(tmp_path):
    ckpt = os.path.join(str(tmp_path), "ck")
    cfg = {**SPILL_CFG, "computer.checkpoint-path": ckpt}
    digest_table.reset()
    g = open_graph(cfg)
    tx = g.new_transaction()
    vs = [tx.add_vertex("person") for _ in range(4)]
    tx.add_edge(vs[0], "knows", vs[1])
    tx.commit()
    g.traversal().V().out("knows").out("knows").count()
    top = digest_table.top(5)
    assert top, "digest table empty after a traversal"
    g.close()
    assert os.path.exists(ckpt + ".pricebook.json")
    digest_table.reset()
    assert digest_table.top(5) == []
    # same backing manager is gone (inmemory), but the PRICE BOOK warm
    # start is about the table, not the data: reopen loads it
    g2 = open_graph(cfg)
    try:
        warmed = {e["digest"]: e for e in digest_table.top(10)}
        assert top[0]["digest"] in warmed
        assert warmed[top[0]["digest"]]["count"] == top[0]["count"]
        assert digest_table.mean_cost_ms(top[0]["digest"]) is not None
    finally:
        g2.close()


def test_price_book_server_table_roundtrip(tmp_path):
    from janusgraph_tpu.observability.profiler import (
        DigestTable,
        load_price_book,
        restore_digest_records,
        save_price_book,
    )

    path = os.path.join(str(tmp_path), "pb.json")
    t = DigestTable()
    for _ in range(5):
        t.observe("abcd1234", "server>g.V().out()", 12.5, cells=100)
    save_price_book(path, {"server": t})
    # a second save of ANOTHER table must preserve the first
    t2 = DigestTable()
    t2.observe("ffff0000", "full-scan>out", 3.0)
    save_price_book(path, {"oltp": t2})
    tables = load_price_book(path)
    assert set(tables) == {"server", "oltp"}
    restored = DigestTable()
    assert restore_digest_records(restored, tables["server"]) == 1
    assert restored.mean_cost_ms("abcd1234") == pytest.approx(12.5)
    top = restored.top(1)[0]
    assert top["count"] == 5 and top["p50_ms"] > 0
    # live entries outrank the file on merge
    restore_digest_records(restored, tables["server"])
    assert restored.top(1)[0]["count"] == 5


# --------------------------------------------------------------- planner unit
def test_recognize_vocabulary():
    g, people, _places = _social_graph()
    try:
        t = g.traversal().V().out("knows").out("knows")
        plan, reason = sp.recognize(t)
        assert plan is not None and len(plan.hops) == 2
        # property has() head is unsupported
        t = g.traversal().V().has("name", "p0").out("knows").out("knows")
        plan, reason = sp.recognize(t)
        assert plan is None and reason.startswith("seed-filter")
        # mid-chain order() is unsupported
        t = g.traversal().V().out("knows").out("knows").order()
        plan, reason = sp.recognize(t)
        assert plan is None
        # repeat() is unsupported (no _expand_meta on the repeat step)
        t = g.traversal().V().repeat(lambda x: x.out("knows"), times=2)
        plan, reason = sp.recognize(t)
        assert plan is None
        # edge expansion mid-chain is unsupported
        t = g.traversal().V().out_e("knows").in_v()
        plan, reason = sp.recognize(t)
        assert plan is None
    finally:
        g.close()


def test_spillover_disabled_config():
    g, _people, _places = _social_graph({"computer.spillover": False})
    try:
        assert g.spillover_planner is None
        base = _spill_count()
        for _ in range(3):
            g.traversal().V().out("knows").out("knows").count()
        assert _spill_count() == base
    finally:
        g.close()


# ------------------------------------------------------ the seed hop on the host
def _seed_hop_graph():
    """48 people (p0 on a self loop, p1 -> p2 a parallel edge): a seed's
    rows stay well under the share of the edges at which hop 0 stays
    dense."""
    g, people, _places = _social_graph(n_people=48, n_places=4)
    return g, people


def _seed_hop_host_count():
    return _spill_count("olap.spillover.seed_hop_host")


def _hop(t, direction, labels):
    return {"out": t.out, "in": t.in_, "both": t.both}[direction](*labels)


def _device_counts(planner, plan, tx, host_hop):
    """The count vector of the plan's program on the executor, over the
    tx-patched snapshot: with hop 0 on the host, or (`host_hop` False)
    OLAPTraversalProgram with ALL hops from the same seed mask."""
    import numpy as np
    from unittest import mock

    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    with planner._lock:
        base = planner._snapshot()
    overlay = sp.tx_overlay(tx)
    csr = sp.patched_csr(base, overlay)
    if host_hop:
        program, edges = planner._compile(plan, csr, overlay)
        assert edges is not None and edges > 0
        assert len(program.steps) == len(plan.hops) - 1
    else:
        with mock.patch.object(sp, "host_seed_hop", lambda *a: None):
            program, edges = planner._compile(plan, csr, overlay)
        assert edges is None and len(program.steps) == len(plan.hops)
    return np.asarray(TPUExecutor(csr).run(program)["count"])


#: (name, seeds as indices into the people, what the tx does before the
#: read, hasLabel after hop 0, hops)
_SEED_HOP_CASES = [
    ("seed-twice", (5, 5), None, False, 2),
    ("two-seeds", (5, 9), None, False, 2),
    ("self-loop", (0,), None, False, 2),
    ("parallel-edges", (1,), None, False, 2),
    ("seed-removed", (5, 9), "remove-seed", False, 2),
    ("edge-added-on-row", (5,), "add-edge", False, 2),
    ("has-label-after-hop0", (5, 9), None, True, 2),
    ("three-hops", (5,), None, False, 3),
]


@pytest.mark.parametrize("labels", [(), ("knows",)], ids=["any", "knows"])
@pytest.mark.parametrize("direction", ["out", "in", "both"])
@pytest.mark.parametrize(
    "case", _SEED_HOP_CASES, ids=[c[0] for c in _SEED_HOP_CASES]
)
def test_seed_hop_on_the_host(case, direction, labels):
    import numpy as np

    from janusgraph_tpu.core.traversal import GraphTraversalSource

    _name, seeds, mutate, has_label, n_hops = case
    g, people = _seed_hop_graph()
    try:
        tx = g.new_transaction()
        if mutate == "remove-seed":
            tx.remove_vertex(tx.get_vertex(people[seeds[0]]))
        elif mutate == "add-edge":
            # both ends of the new edge are read: it lies on the seed's
            # out-row and on its in-row
            v5, v7 = tx.get_vertex(people[5]), tx.get_vertex(people[7])
            tx.add_edge(v5, "knows", v7)
            tx.add_edge(v7, "knows", v5)

        def build():
            t = GraphTraversalSource(g, tx).V(*[people[i] for i in seeds])
            t = _hop(t, direction, labels)
            if has_label:
                t = t.has_label("person")
            for _ in range(n_hops - 1):
                t = _hop(t, direction, labels)
            return t.id_()

        planner = g.spillover_planner
        build().to_list()  # teach
        spilled_before, host_before = _spill_count(), _seed_hop_host_count()
        spilled = sorted(build().to_list())
        assert _spill_count() == spilled_before + 1, "did not spill"
        assert _seed_hop_host_count() == host_before + 1
        info = registry.last_run("olap.spillover")
        block = info["spillover"]
        assert block["fallback"] is None
        assert block["seed_hop"] == "host" and block["seed_hop_edges"] > 0
        assert block["hops"] == n_hops
        assert info["supersteps"] == n_hops - 1
        assert info["executor"] == "host-loop"
        planner.enabled = False
        try:
            row = sorted(build().to_list())
        finally:
            planner.enabled = True
        assert spilled == row
        # the whole count vector, against every hop on the executor
        plan, _reason = sp.recognize(build())
        host = _device_counts(planner, plan, tx, host_hop=True)
        dense = _device_counts(planner, plan, tx, host_hop=False)
        assert np.array_equal(host, dense)
        tx.rollback()
    finally:
        g.close()


@pytest.mark.parametrize("start", [
    "all-vertices", "has-label-only", "ids-that-cover-the-graph",
    "labelled-hop-without-edge-types",
])
def test_seed_hop_stays_on_the_device(start):
    import dataclasses

    g, people = _seed_hop_graph()
    try:
        def build():
            t = g.traversal()
            if start == "has-label-only":
                return t.V().has_label("person").out().out().id_()
            if start == "ids-that-cover-the-graph":
                return t.V(*people).out().out().id_()
            if start == "labelled-hop-without-edge-types":
                return t.V(people[5]).out("knows").out().id_()
            return t.V().out().out().id_()

        planner = g.spillover_planner
        build().to_list()  # teach
        if start == "labelled-hop-without-edge-types":
            sorted(build().to_list())  # packs the snapshot
            with planner._lock:
                planner._csr = dataclasses.replace(
                    planner._csr, out_edge_type=None, in_edge_type=None
                )
                planner._tpu_ex = None
                plan, _reason = sp.recognize(build())
                program, edges = planner._compile(
                    plan, planner._csr, sp.tx_overlay(g.new_transaction())
                )
            # the choice is the device's, whose pack then refuses labels
            # without types as it always did: the row path answers
            assert edges is None and len(program.steps) == 2
        spilled_before, host_before = _spill_count(), _seed_hop_host_count()
        got = sorted(build().to_list())
        assert _seed_hop_host_count() == host_before
        if start == "labelled-hop-without-edge-types":
            assert _spill_count() == spilled_before
            assert registry.last_run("olap.spillover")["spillover"][
                "fallback"
            ].startswith("error:ValueError")
        else:
            assert _spill_count() == spilled_before + 1
            info = registry.last_run("olap.spillover")
            assert info["spillover"]["seed_hop"] == "device"
            assert info["spillover"]["seed_hop_edges"] == 0
            assert info["supersteps"] == 2
        planner.enabled = False
        try:
            assert got == sorted(build().to_list())
        finally:
            planner.enabled = True
    finally:
        g.close()


# ------------------------------------------------------------ the lock's ledger
class _Turnstile:
    """Stands in for the planner's lock: a thread passes a take only when
    the test lets it through, so the test decides who holds the lock in
    what order, and exactly one staged thread moves at a time."""

    def __init__(self, lock):
        self._lock = lock
        self._at_gate = queue.Queue()
        self._standing = set()
        self._go = collections.defaultdict(lambda: threading.Semaphore(0))
        self.exits = 0  # takes that have been released

    def __enter__(self):
        name = threading.current_thread().name
        self._at_gate.put(name)
        assert self._go[name].acquire(timeout=60), f"{name} never let in"
        self._lock.acquire()

    def __exit__(self, *exc):
        self._lock.release()
        self.exits += 1

    def arrives(self, name):
        """Wait until thread `name` stands at a take, and leave it there."""
        assert self._at_gate.get(timeout=60) == name
        self._standing.add(name)

    def admit(self, name, takes=1):
        """Let `name` through its next `takes` takes, one after another."""
        for _ in range(takes):
            if name not in self._standing:
                self.arrives(name)
            self._standing.remove(name)
            self._go[name].release()


def test_lock_ledger_counts_queue_overtakes_handoffs_and_free_time(
        monkeypatch):
    """Four threads staged through the planner's lock on an injected
    clock. `a` runs alone (FREE time: nobody was asking) while `b` and `c`
    arrive; `d` arrives after a's release and is let in first, overtaking
    both (a HAND-OFF all the same: the lock was wanted while it stood
    free); then `c` before `b`; then `a` again with nobody waiting."""
    from janusgraph_tpu.observability import tracer

    g, people, _ = _social_graph()
    try:
        planner = g.spillover_planner

        def count():
            return g.traversal().V(people[0]).out("knows").out(
                "knows").count()

        want = count()  # teach the shape ...
        assert count() == want  # ... spill it once: compiles, and releases
        gate = _Turnstile(planner._lock)
        monkeypatch.setattr(planner, "_lock", gate)

        class Clock:  # moves only when the test says so
            now = planner._released_ns  # the teaching run's release

        monkeypatch.setattr(tracer, "_clock", lambda: Clock.now)
        def gaps():  # (observations, ns) of the ledger's two timers
            return {
                name: (registry.timer(name).count,
                       registry.timer(name).total_ns)
                for name in ("spill.lock_handoff", "spill.lock_free")
            }

        before = gaps()
        seen = _spill_count("olap.spillover.lock.waiters_seen")
        overtakes = _spill_count("olap.spillover.lock.overtakes")
        spilled = _spill_count()
        answers, again = {}, threading.Event()

        def client(name, twice=False):
            answers[name] = [count()]
            if twice:
                assert again.wait(60)
                answers[name].append(count())

        threads = {
            name: threading.Thread(
                target=client, args=(name, name == "a"), name=name)
            for name in "abcd"
        }
        at = dict(before)

        def finished(n):
            """Once run `n` has spilled and released the lock (two takes a
            request): its record's lock fields, and what it added to the
            ledger's timers ({timer: ns}, one of the two)."""
            deadline = time.monotonic() + 60
            while _spill_count() < spilled + n or gate.exits < 2 * n:
                assert time.monotonic() < deadline, f"run {n} never ended"
                time.sleep(0.002)
            record = registry.last_run("olap.spillover")["spillover"]
            now = gaps()
            moved = {name[len("spill.lock_"):]: ns - at[name][1]
                     for name, (seen_, ns) in now.items()
                     if seen_ != at[name][0]}
            at.update(now)
            return record["queue_depth"], record["overtook"], moved

        # a arrives 1,000 ns after the teaching run's release
        Clock.now += 1_000
        threads["a"].start()
        gate.admit("a")          # the promotion check
        gate.arrives("a")        # a stands at the second take
        for name in "bc":        # b, then c, arrive and stand at the first
            Clock.now += 10
            threads[name].start()
            gate.arrives(name)
        Clock.now += 5
        gate.admit("a")          # held 1,025 ns after that release
        # it had not arrived by that release: free time
        assert finished(1) == (2, 0, {"free": 1_025})
        # a released at the instant it was held (the clock stood still);
        # d arrives 40 us later and is let through both takes at once
        Clock.now += 40_000
        threads["d"].start()
        gate.arrives("d")
        Clock.now += 2_000_000
        gate.admit("d", takes=2)
        # d came after a's release, b and c before it: the lock was wanted
        assert finished(2) == (2, 2, {"handoff": 2_040_000})
        Clock.now += 3_000_000
        gate.admit("c", takes=2)
        assert finished(3) == (1, 1, {"handoff": 3_000_000})
        Clock.now += 500_000
        gate.admit("b", takes=2)
        assert finished(4) == (0, 0, {"handoff": 500_000})
        # nobody waits: a's second request arrives 700 ns after b's release
        Clock.now += 700
        again.set()
        gate.admit("a", takes=2)
        assert finished(5) == (0, 0, {"free": 700})
        for th in threads.values():
            th.join(60)
            assert not th.is_alive()
        assert answers == {"a": [want, want], "b": [want], "c": [want],
                           "d": [want]}
        assert planner._waiting == {}
        assert not {"lock_wait_ms", "handoff_ms"} & set(
            registry.last_run("olap.spillover")["spillover"])
        assert {
            name: (count - before[name][0], ns - before[name][1])
            for name, (count, ns) in gaps().items()
        } == {
            "spill.lock_handoff": (3, 2_040_000 + 3_000_000 + 500_000),
            "spill.lock_free": (2, 1_025 + 700),
        }
        assert _spill_count(
            "olap.spillover.lock.waiters_seen") == seen + 2 + 2 + 1
        assert _spill_count(
            "olap.spillover.lock.overtakes") == overtakes + 2
    finally:
        g.close()


def test_a_request_that_leaves_before_the_plan_leaves_the_queue():
    """An unpromoted shape takes a ticket, passes the promotion check and
    goes back to the row path: it stands in the queue no longer, and holds
    no one's `queue_depth` up."""
    g, people, _ = _social_graph({"computer.spillover-min-seen": 1000})
    try:
        planner = g.spillover_planner
        before = _spill_count("olap.spillover.lock.waiters_seen")
        for _ in range(3):
            g.traversal().V(people[0]).out("knows").out("knows").count()
        assert planner._waiting == {}
        assert _spill_count() == 0 or planner._promoted == {}
        assert _spill_count("olap.spillover.lock.waiters_seen") == before
    finally:
        g.close()


def test_a_hold_that_is_refused_counts_in_no_share(monkeypatch):
    """The lock's two counters are written where `olap.spillover.spilled`
    is, so a hold whose plan is refused under the lock is in neither side
    of `lock_queue_depth` and `lock_overtake_share`."""
    from janusgraph_tpu.olap import spillover

    g, people, _ = _social_graph()
    try:
        planner = g.spillover_planner

        def count():
            return g.traversal().V(people[0]).out("knows").out(
                "knows").count()

        want = count()  # teach the shape ...
        spilled = _spill_count()
        assert count() == want and _spill_count() == spilled + 1
        names = ("olap.spillover.spilled", "olap.spillover.lock.waiters_seen",
                 "olap.spillover.lock.overtakes")
        before = [_spill_count(n) for n in names]
        # someone stands at the lock, and has for long
        planner._waiting[-1] = 0

        def refuse(*args, **kwargs):
            raise spillover._SpillRefused("staged")

        monkeypatch.setattr(planner, "_snapshot", refuse)
        assert count() == want  # the row path answers
        assert [_spill_count(n) for n in names] == before
        monkeypatch.undo()
        assert count() == want
        assert [_spill_count(n) for n in names] == [
            before[0] + 1, before[1] + 1, before[2] + 1]
    finally:
        g.close()
