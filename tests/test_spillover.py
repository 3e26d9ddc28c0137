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
        with planner._state:
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

    with planner._state:
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
            with planner._lock, planner._state:
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
    """Four threads staged through the device's lock on an injected clock,
    each asking a shape of its own so that no holder can take another
    along. `a` runs alone (FREE time: nobody was asking) while `b` and `c`
    arrive; `d` arrives after a's release and is let in first, overtaking
    both (a HAND-OFF all the same: the lock was wanted while it stood
    free); then `c` before `b`; then `a` again with nobody waiting."""
    from janusgraph_tpu.observability import tracer

    g, people, _ = _social_graph()
    try:
        planner = g.spillover_planner
        t = g.traversal
        asks = {
            "a": lambda: t().V(people[0]).out("knows").out("knows").count(),
            "b": lambda: t().V(people[0]).out("knows").out("lives").count(),
            "c": lambda: t().V(people[0]).out("knows").in_("knows").count(),
            "d": lambda: t().V(people[0]).in_("knows").out("knows").count(),
        }
        want = {}
        for name, ask in asks.items():
            want[name] = ask()  # teach the shape ...
            assert ask() == want[name]  # ... spill it once: it compiles
        gate = _Turnstile(planner._lock)
        monkeypatch.setattr(planner, "_lock", gate)

        class Clock:  # moves only when the test says so
            now = planner._released_ns  # the teaching runs' last release

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
        dispatched = _spill_count("olap.spillover.dispatches")
        answers, again = {}, threading.Event()

        def client(name, twice=False):
            answers[name] = [asks[name]()]
            if twice:
                assert again.wait(60)
                answers[name].append(asks[name]())

        threads = {
            name: threading.Thread(
                target=client, args=(name, name == "a"), name=name)
            for name in "abcd"
        }
        at = dict(before)

        def finished(n):
            """Once run `n` has spilled and released the lock (one take a
            request): its record's lock fields, and what it added to the
            ledger's timers ({timer: ns}, one of the two)."""
            deadline = time.monotonic() + 60
            while _spill_count() < spilled + n or gate.exits < n:
                assert time.monotonic() < deadline, f"run {n} never ended"
                time.sleep(0.002)
            record = registry.last_run("olap.spillover")["spillover"]
            assert (record["batch"], record["led"]) == (1, True)
            now = gaps()
            moved = {name[len("spill.lock_"):]: ns - at[name][1]
                     for name, (seen_, ns) in now.items()
                     if seen_ != at[name][0]}
            at.update(now)
            return record["queue_depth"], record["overtook"], moved

        # a arrives 1,000 ns after the teaching runs' last release
        Clock.now += 1_000
        threads["a"].start()
        gate.arrives("a")        # a stands at the lock, its plan made
        for name in "bc":        # b, then c, arrive, plan and stand
            Clock.now += 10
            threads[name].start()
            gate.arrives(name)
        Clock.now += 5
        gate.admit("a")          # held 1,025 ns after that release
        # it had not arrived by that release: free time
        assert finished(1) == (2, 0, {"free": 1_025})
        # a released at the instant it was held (the clock stood still);
        # d arrives 40 us later and is let in first
        Clock.now += 40_000
        threads["d"].start()
        gate.arrives("d")
        Clock.now += 2_000_000
        gate.admit("d")
        # d came after a's release, b and c before it: the lock was wanted
        assert finished(2) == (2, 2, {"handoff": 2_040_000})
        Clock.now += 3_000_000
        gate.admit("c")
        assert finished(3) == (1, 1, {"handoff": 3_000_000})
        Clock.now += 500_000
        gate.admit("b")
        assert finished(4) == (0, 0, {"handoff": 500_000})
        # nobody waits: a's second request arrives 700 ns after b's release
        Clock.now += 700
        again.set()
        gate.admit("a")
        assert finished(5) == (0, 0, {"free": 700})
        for th in threads.values():
            th.join(60)
            assert not th.is_alive()
        assert answers == {"a": [want["a"]] * 2, "b": [want["b"]],
                           "c": [want["c"]], "d": [want["d"]]}
        assert planner._waiting == {} and planner._pending == {}
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
        assert _spill_count(
            "olap.spillover.dispatches") == dispatched + 5
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


# ------------------------------------------------- the holder's dispatch
class _Stage:
    """Client threads staged through the planner's lock (`_Turnstile`)
    with the planner's run function wrapped: the test decides who stands
    at the lock when it is taken, and sees every dispatch (`runs`: the
    width of the program's start, 0 for the (n,) one, and whether its
    snapshot was patched)."""

    def __init__(self, planner, monkeypatch, run=None):
        self.gate = _Turnstile(planner._lock)
        monkeypatch.setattr(planner, "_lock", self.gate)
        self.runs = []
        real = planner._run_program

        def recorded(csr, program, patched):
            self.runs.append((program.width, patched))
            return (run or real)(csr, program, patched)

        self.real = real
        monkeypatch.setattr(planner, "_run_program", recorded)
        self.answers, self.threads = {}, {}

    def client(self, name, ask):
        def body():
            try:
                self.answers[name] = ask()
            except Exception as e:  # noqa: BLE001 - the answer IS the error
                self.answers[name] = e

        self.threads[name] = threading.Thread(target=body, name=name)
        self.threads[name].start()

    def plans(self, name, ask):
        """Start `name` and leave it standing at the device's lock, past
        the planner's short lock and with its plan made."""
        self.client(name, ask)
        self.gate.arrives(name)

    def holds(self, name):
        """Let `name` take the lock it stands at, and wait until it has
        answered (a holder's dispatch, or a member finding its column)."""
        self.gate.admit(name)
        self.threads[name].join(60)
        assert not self.threads[name].is_alive(), f"{name} never answered"
        return self.answers[name]


def _dispatches():
    return _spill_count("olap.spillover.dispatches")


def _taught(extra_cfg=None, n_people=40):
    """A graph whose two-hop shape is promoted, spilled once (so both
    widths of its step are prepared), `ask(i)` for person i's distinct
    two-hop count through the planner, and the row path's answers."""
    g, people, places = _social_graph(extra_cfg, n_people=n_people)
    planner = g.spillover_planner

    def ask(i, source=None):
        return (source or g.traversal()).V(people[i]).out("knows").out(
            "knows").dedup().count()

    def oracle():
        planner.enabled = False
        try:
            return [ask(i) for i in range(len(people))]
        finally:
            planner.enabled = True

    want = oracle()
    before = _spill_count()
    assert ask(0) == want[0] and _spill_count() == before + 1
    return g, people, planner, ask, oracle, want


def _batches(n):
    """(batch, led) of the newest n spilled run records."""
    runs = registry.runs("olap.spillover")[-n:]
    return sorted(
        (r["spillover"]["batch"], r["spillover"]["led"]) for r in runs)


def test_three_requests_at_the_lock_leave_in_one_dispatch(monkeypatch):
    """(i) Three compatible requests stand at the lock; the one that
    takes it runs all three as columns of ONE program, each answer the
    row path's for ITS start."""
    g, people, planner, ask, _, want = _taught()
    try:
        trio = [0, 1, 2]
        assert len({want[i] for i in trio}) == 3, want
        stage = _Stage(planner, monkeypatch)
        spilled, dispatched = _spill_count(), _dispatches()
        seen = _spill_count("olap.spillover.lock.waiters_seen")
        for name, i in zip("abc", trio):
            stage.plans(name, lambda i=i: ask(i))
        assert len(planner._pending) == 3
        assert stage.holds("b") == want[1]  # the holder: anyone will do
        assert stage.runs == [(sp.BATCH_WIDTH, False)]
        assert planner._pending == {} and planner._waiting == {}
        # the members find their columns when they reach the lock
        assert stage.holds("a") == want[0]
        assert stage.holds("c") == want[2]
        assert stage.runs == [(sp.BATCH_WIDTH, False)]
        assert _spill_count() == spilled + 3
        assert _dispatches() == dispatched + 1
        assert _batches(3) == [(3, False), (3, False), (3, True)]
        # the queue a holder finds is a dispatch's, counted once
        assert _spill_count(
            "olap.spillover.lock.waiters_seen") == seen + 2
        records = registry.runs("olap.spillover")[-3:]
        assert {r["spillover"]["queue_depth"] for r in records} == {2}
        assert {r["spillover"]["overtook"] for r in records} == {0}
        assert {r["supersteps"] for r in records} == {1}
        # the next batch is stacked where this one was: two riders write
        # their columns over the trio's, the third column keeps c's start
        # and nobody reads it
        assert len({want[i] for i in (7, 9)} | {want[1]}) == 3, want
        stage.plans("d", lambda: ask(7))
        stage.plans("e", lambda: ask(9))
        assert stage.holds("e") == want[9]
        assert stage.holds("d") == want[7]
        assert stage.runs == [(sp.BATCH_WIDTH, False)] * 2
        assert _batches(2) == [(2, False), (2, True)]
    finally:
        g.close()


def test_a_lone_request_runs_the_narrow_program(monkeypatch):
    """(v) Nobody stands at the lock: today's (n,) program, one dispatch
    a request."""
    g, people, planner, ask, _, want = _taught()
    try:
        stage = _Stage(planner, monkeypatch)
        spilled, dispatched = _spill_count(), _dispatches()
        stage.plans("a", lambda: ask(3))
        assert stage.holds("a") == want[3]
        assert stage.runs == [(0, False)]
        assert (_spill_count(), _dispatches()) == (
            spilled + 1, dispatched + 1)
        assert _batches(1) == [(1, True)]
    finally:
        g.close()


def test_the_first_spilled_request_of_a_shape_prepares_both_widths():
    """The wide step is compiled where the narrow one is: by the first
    spilled request of its shape on an executor, which runs alone."""
    g, people, planner, ask, _, want = _taught()
    try:
        def widths():
            return sorted(
                dict(key[0][2])["width"] for key in planner._tpu_ex._prepared)

        assert widths() == [0, sp.BATCH_WIDTH]
        dispatched = _dispatches()
        assert ask(1) == want[1]  # nothing more to prepare
        assert widths() == [0, sp.BATCH_WIDTH]
        assert _dispatches() == dispatched + 1

        def three(i):
            return g.traversal().V(people[i]).out("knows").out(
                "knows").out("lives").count()

        row = three(2)  # taught ...
        assert three(2) == row  # ... and spilled: two steps on the device
        assert widths() == sorted([0, sp.BATCH_WIDTH] * 3)
    finally:
        g.close()


def test_incompatible_requests_run_alone_and_the_rest_still_batch(
        monkeypatch):
    """(ii) At the lock stand: `d`, planned against a snapshot a commit
    has since replaced; `b`, whose transaction holds an uncommitted edge;
    `c`, of another shape; `e` and `f`, plain. The holder `e` takes `f`
    and nobody else; the others run alone, `d` on the refreshed
    snapshot."""
    from janusgraph_tpu.core.traversal import GraphTraversalSource

    g, people, planner, ask, oracle, _ = _taught(
        {"computer.spillover-max-staleness": 10_000})
    try:
        def other(i):
            return g.traversal().V(people[i]).out("knows").out(
                "lives").dedup().count()

        planner.enabled = False
        other_want = other(4)
        planner.enabled = True
        assert other(4) == other_want  # taught ...
        assert other(4) == other_want  # ... spilled, its widths prepared
        stage = _Stage(planner, monkeypatch)
        stage.plans("d", lambda: ask(5))
        old = planner._csr
        tx = g.new_transaction()
        tx.add_edge(tx.get_vertex(people[5]), "knows",
                    tx.get_vertex(people[7]))
        tx.commit()
        want = oracle()
        txb = g.new_transaction()
        txb.add_edge(txb.get_vertex(people[6]), "knows",
                     txb.get_vertex(people[9]))
        src = GraphTraversalSource(g, txb)
        planner.enabled = False
        want_b = ask(6, src)
        planner.enabled = True
        stage.plans("b", lambda: ask(6, src))  # refreshes the snapshot
        assert planner._csr is not old
        stage.plans("c", lambda: other(4))
        stage.plans("e", lambda: ask(1))
        stage.plans("f", lambda: ask(2))
        spilled, dispatched = _spill_count(), _dispatches()
        assert stage.holds("e") == want[1]
        assert stage.runs == [(sp.BATCH_WIDTH, False)]
        assert len(planner._pending) == 3  # b, c, d still stand
        assert stage.holds("f") == want[2]
        assert _batches(2) == [(2, False), (2, True)]
        # e overtook the three it left standing, all earlier than it
        assert registry.runs("olap.spillover")[-1]["spillover"][
            "overtook"] == 3
        assert stage.holds("b") == want_b
        assert stage.runs[-1] == (0, True)  # alone, on its patched snapshot
        assert stage.holds("c") == other_want
        assert stage.holds("d") == want[5]  # with the commit
        # c's shape is the first of its kind on the refreshed snapshot's
        # executor, so its wide step is prepared behind the narrow run;
        # d's shape ran there already (e's dispatch)
        assert stage.runs[2:] == [(0, False), (sp.BATCH_WIDTH, False),
                                  (0, False)]
        assert _spill_count() == spilled + 5
        assert _dispatches() == dispatched + 4
        assert _batches(3) == [(1, True)] * 3
        txb.rollback()
    finally:
        g.close()


@pytest.mark.parametrize("holder", ["earlier", "later"])
def test_a_commit_between_two_arrivals(monkeypatch, holder):
    """(vi) `x` plans, a commit lands, `y` arrives and its take refreshes
    the snapshot. Whoever holds the lock first, no answer comes from a
    snapshot older than the one a check after its arrival accepts: `x`
    is planned again on the refreshed snapshot (as the holder, with `y`
    riding; or alone, after `y` left it standing)."""
    g, people, planner, ask, oracle, stale = _taught(
        {"computer.spillover-max-staleness": 10_000})
    try:
        stage = _Stage(planner, monkeypatch)
        stage.plans("x", lambda: ask(5))
        tx = g.new_transaction()
        tx.add_edge(tx.get_vertex(people[5]), "knows",
                    tx.get_vertex(people[7]))
        tx.add_edge(tx.get_vertex(people[7]), "knows",
                    tx.get_vertex(people[10]))
        tx.commit()
        want = oracle()
        assert want[5] != stale[5]
        stage.plans("y", lambda: ask(7))
        dispatched = _dispatches()
        if holder == "earlier":
            assert stage.holds("x") == want[5]
            assert stage.holds("y") == want[7]
            assert _dispatches() == dispatched + 1
            assert stage.runs == [(sp.BATCH_WIDTH, False)]
        else:
            assert stage.holds("y") == want[7]
            assert len(planner._pending) == 1
            assert stage.holds("x") == want[5]
            assert _dispatches() == dispatched + 2
            # y ran alone, the first of its shape on the refreshed
            # snapshot's executor (the wide step prepared behind it)
            assert [w for w, _ in stage.runs] == [0, sp.BATCH_WIDTH, 0]
    finally:
        g.close()


@pytest.mark.parametrize("fault", ["count-overflow", "max-traversers"])
def test_a_member_is_judged_alone(monkeypatch, fault):
    """(iii) One member's column passes 2^24 (it falls back to the row
    path), or its output passes `query.max-traversers` (it raises): the
    others of the dispatch keep their answers."""
    import numpy as np

    from janusgraph_tpu.exceptions import QueryError

    g, people, planner, ask, _, want = _taught()
    try:
        def ids(i):
            return sorted(g.traversal().V(people[i]).out("knows").out(
                "knows").id_().to_list())

        planner.enabled = False
        rows = [ids(i) for i in range(3)]
        planner.enabled = True
        assert ids(0) == rows[0] and ids(0) == rows[0]  # taught, spilled
        stage = None

        def run(csr, program, patched):
            states = stage.real(csr, program, patched)
            if fault == "count-overflow" and program.width:
                counts = np.array(states["count"])
                counts[0, 1] = float(1 << 24)  # b's column
                states = {**states, "count": counts}
            return states

        stage = _Stage(planner, monkeypatch, run=run)
        if fault == "max-traversers":
            sizes = sorted(len(r) for r in rows)
            assert sizes[1] < sizes[2]
            monkeypatch.setattr(g, "_max_traversers", sizes[1])
        fallbacks = _spill_count("olap.spillover.fallback")
        spilled = _spill_count()
        for name, i in zip("abc", range(3)):
            stage.plans(name, lambda i=i: ids(i))
        got = {"a": stage.holds("a")}
        assert stage.runs == [(sp.BATCH_WIDTH, False)]
        got.update(b=stage.holds("b"), c=stage.holds("c"))
        assert stage.runs == [(sp.BATCH_WIDTH, False)]
        if fault == "count-overflow":
            assert [got[n] for n in "abc"] == rows
            assert _spill_count() == spilled + 2
            assert _spill_count("olap.spillover.fallback") == fallbacks + 1
            assert registry.last_run("olap.spillover")["spillover"][
                "fallback"] is None  # c's, the newest: spilled
        else:
            over = [len(r) > g._max_traversers for r in rows]
            assert any(over) and not all(over)
            for name, row, refused in zip("abc", rows, over):
                if refused:
                    assert isinstance(got[name], QueryError), got[name]
                else:
                    assert got[name] == row
            assert _spill_count() == spilled + over.count(False)
            assert _spill_count("olap.spillover.fallback") == fallbacks
    finally:
        g.close()


def test_a_dispatch_that_raises_sends_every_member_to_the_row_path(
        monkeypatch):
    """(iv) The dispatch itself fails: every member falls back, each
    counted, each answered by the row path."""
    g, people, planner, ask, _, want = _taught()
    try:
        def run(csr, program, patched):
            raise RuntimeError("staged device fault")

        stage = _Stage(planner, monkeypatch, run=run)
        fallbacks = _spill_count("olap.spillover.fallback")
        errors = _spill_count("olap.spillover.fallback.error")
        spilled, dispatched = _spill_count(), _dispatches()
        for name, i in zip("abc", (0, 1, 5)):
            stage.plans(name, lambda i=i: ask(i))
        assert stage.holds("a") == want[0]
        assert planner._pending == {}
        assert stage.holds("b") == want[1]
        assert stage.holds("c") == want[5]
        assert stage.runs == [(sp.BATCH_WIDTH, False)]
        assert _spill_count("olap.spillover.fallback") == fallbacks + 3
        assert _spill_count("olap.spillover.fallback.error") == errors + 3
        assert (_spill_count(), _dispatches()) == (spilled, dispatched)
        reason = registry.last_run("olap.spillover")["spillover"]["fallback"]
        assert reason.startswith("error:RuntimeError: staged device fault")
        monkeypatch.undo()
        assert planner.promotion_snapshot()[
            sp.traversal_digest(g.traversal().V(people[0]).out(
                "knows").out("knows").dedup())[1]]["fallbacks"] == 3
    finally:
        g.close()


def test_concurrent_clients_each_get_their_own_answer():
    """Unstaged: more clients than the wide start has columns, a short
    switch interval, no barriers. Every answer is the row path's for its
    own start, every request is counted once (the per-digest tally is
    written from the clients' own threads), and nobody is left standing."""
    import sys

    g, people, planner, ask, _, want = _taught()
    clients, rounds = sp.BATCH_WIDTH + 4, 12
    digest = sp.traversal_digest(g.traversal().V(people[0]).out(
        "knows").out("knows").dedup())[1]
    tally = planner.promotion_snapshot()[digest]["spilled"]
    spilled, dispatched = _spill_count(), _dispatches()
    wrong, interval = [], sys.getswitchinterval()

    def client(k):
        for r in range(rounds):
            i = (5 * k + r) % len(people)
            got = ask(i)
            if got != want[i]:
                wrong.append((k, i, got, want[i]))

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(clients)]
    try:
        sys.setswitchinterval(1e-5)
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(interval)
        g.close()
    assert wrong == []
    assert _spill_count() == spilled + clients * rounds
    assert planner.promotion_snapshot()[digest]["spilled"] == (
        tally + clients * rounds)
    assert dispatched < _dispatches() <= dispatched + clients * rounds
    assert planner._pending == {} and planner._waiting == {}
    assert max(r["spillover"]["batch"]
               for r in registry.runs("olap.spillover")) <= sp.BATCH_WIDTH
