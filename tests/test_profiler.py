"""Roofline profiler, resource ledger, digest table, flame export (PR 5).

Covers the acceptance properties:

- one driver query against a remote-store-backed server yields a single
  trace whose ledger totals (cells read, bytes moved) equal the sum of
  the ``ledger.*`` span annotations over that trace, and the flame
  export of the same trace emits valid collapsed-stack lines;
- ledger propagation negotiates its feature bit in BOTH directions (new
  client <-> old server, old client <-> new server) over the remote
  store AND index protocols, mirroring the PR 4 trace-header tests;
- TPU/CPU pagerank run records report flops, bytes, operational
  intensity, and roofline utilization for every superstep — via XLA
  cost_analysis AND via the host estimator fallback;
- ``.profile()`` returns a ``resources`` block in the ledger vocabulary;
- slow-op and flight ``slow_span`` events carry the query digest.
"""

import json
import re
import time
import urllib.request

import pytest

from janusgraph_tpu.core.graph import open_graph
from janusgraph_tpu.driver import JanusGraphClient
from janusgraph_tpu.observability import tracer
from janusgraph_tpu.observability.profiler import (
    ResourceLedger,
    accrue,
    current_ledger,
    digest_table,
    encode_ledger_block,
    flame_lines,
    ledger_scope,
    shape_digest,
    split_ledger_block,
    traversal_shape,
)
from janusgraph_tpu.server import JanusGraphManager, JanusGraphServer
from janusgraph_tpu.storage.inmemory import InMemoryStoreManager
from janusgraph_tpu.storage.kcvs import KeySliceQuery, SliceQuery
from janusgraph_tpu.storage.remote import (
    RemoteStoreManager,
    RemoteStoreServer,
)

_SLICE = SliceQuery(b"", b"\xff")


def _span_ledger_sum(trace, field):
    """Sum of one ledger.* annotation over every span of a trace."""
    total = 0

    def walk(span):
        nonlocal total
        total += int(span.attrs.get(f"ledger.{field}", 0))
        for c in span.children:
            walk(c)

    for root in trace:
        walk(root)
    return total


def _wait_trace(trace_id, pred, timeout_s=2.0):
    """Remote handlers finish their spans just after replying — poll the
    stitched trace until `pred` holds (or time out and return anyway)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        trace = tracer.find_trace(trace_id)
        if pred(trace):
            return trace
        time.sleep(0.01)
    return tracer.find_trace(trace_id)


# ------------------------------------------------------------------ ledger
def test_ledger_scope_nesting_merges_to_parent():
    with ledger_scope() as outer:
        accrue(cells_read=1)
        with ledger_scope() as inner:
            accrue(cells_read=2, index_hits=3)
        assert inner.get("cells_read") == 2
    assert outer.get("cells_read") == 3
    assert outer.get("index_hits") == 3
    assert current_ledger() is None


def test_accrue_annotates_current_span_aggregating():
    with ledger_scope() as led:
        with tracer.span("work") as sp:
            accrue(cells_read=2)
            accrue(cells_read=3, bytes_read=10)
    assert sp.attrs["ledger.cells_read"] == 5
    assert sp.attrs["ledger.bytes_read"] == 10
    assert led.get("cells_read") == 5


def test_accrue_is_noop_outside_scope():
    with tracer.span("unprofiled") as sp:
        accrue(cells_read=99)
    assert "ledger.cells_read" not in sp.attrs


def test_ledger_wall_by_layer_and_to_dict():
    led = ResourceLedger()
    led.add(cells_read=4)
    led.add_wall("storage", 1.5)
    led.add_wall("storage", 0.5)
    d = led.to_dict()
    assert d["cells_read"] == 4
    assert d["wall_ms_by_layer"]["storage"] == 2.0


def test_ledger_block_codec_roundtrip_and_degradation():
    fields = {"cells_read": 7, "bytes_written": 1 << 40, "wall_ns": 123}
    blob = encode_ledger_block(fields) + b"PAYLOAD"
    decoded, rest = split_ledger_block(blob)
    assert decoded == fields
    assert rest == b"PAYLOAD"
    # malformed blocks degrade to None without consuming the body
    assert split_ledger_block(b"") == (None, b"")
    garbage = bytes([200]) + b"\x01"
    assert split_ledger_block(garbage) == (None, garbage)


# ------------------------------------------------- remote store wire compat
@pytest.fixture
def served():
    server = RemoteStoreServer(InMemoryStoreManager()).start()
    host, port = server.address
    mgr = RemoteStoreManager(host, port)
    yield server, mgr
    mgr.close()
    server.stop()


def test_ledger_echo_over_remote_store(served):
    """new client <-> new server: flagged ops come back with an echoed
    ledger block; the storage node's span carries the same fields."""
    _server, mgr = served
    store = mgr.open_database("edgestore")
    tx = mgr.begin_transaction()
    store.mutate(b"k", [(b"c1", b"v1"), (b"c2", b"v2")], [], tx)
    with ledger_scope() as led:
        with tracer.span("client.root") as root:
            entries = store.get_slice(KeySliceQuery(b"k", _SLICE), tx)
    assert len(entries) == 2
    assert mgr._remote_ledger is True
    assert led.get("cells_read") == 2
    assert led.get("bytes_read") == sum(
        len(c) + len(v) for c, v in entries
    )
    assert "store.remote" in led.to_dict().get("wall_ms_by_layer", {})
    trace = _wait_trace(
        root.trace_id,
        lambda t: any(s.name == "store.remote.getSlice" for s in t),
    )
    assert led.get("cells_read") == _span_ledger_sum(trace, "cells_read")


def test_new_client_against_old_server_falls_back_to_local_counting():
    server = RemoteStoreServer(
        InMemoryStoreManager(), ledger_echo=False
    ).start()
    host, port = server.address
    mgr = RemoteStoreManager(host, port)
    try:
        store = mgr.open_database("edgestore")
        tx = mgr.begin_transaction()
        store.mutate(b"k", [(b"c", b"vv")], [], tx)
        with ledger_scope() as led:
            with tracer.span("client.oldsrv") as root:
                store.get_slice(KeySliceQuery(b"k", _SLICE), tx)
        assert mgr._remote_ledger is False  # negotiated OFF
        # the client counted decoded entries locally, annotating ITS span
        assert led.get("cells_read") == 1
        assert root.attrs.get("ledger.cells_read") == 1
    finally:
        mgr.close()
        server.stop()


def test_old_client_against_new_server_stays_byte_compatible(served):
    """resource_ledger=False = a pre-ledger client: frames never carry the
    flag, the server replies with plain payloads."""
    _server, _ = served
    host, port = _server.address
    old = RemoteStoreManager(host, port, resource_ledger=False)
    try:
        store = old.open_database("edgestore")
        tx = old.begin_transaction()
        store.mutate(b"k", [(b"c", b"v")], [], tx)
        with ledger_scope() as led:
            entries = store.get_slice(KeySliceQuery(b"k", _SLICE), tx)
        assert entries == [(b"c", b"v")]
        # no echo, no local counting: the client is ledger-oblivious
        assert led.get("cells_read") == 0
    finally:
        old.close()


def test_scan_counts_rows_client_side(served):
    _server, mgr = served
    store = mgr.open_database("edgestore")
    tx = mgr.begin_transaction()
    for i in range(5):
        store.mutate(b"row%d" % i, [(b"c", b"v%d" % i)], [], tx)
    with ledger_scope() as led:
        rows = list(store.get_keys(_SLICE, tx))
    assert len(rows) == 5
    assert led.get("cells_read") == 5
    assert led.get("bytes_read") > 0


# ------------------------------------------------- remote index wire compat
def _index_fixture(ledger_echo=True):
    from janusgraph_tpu.indexing.memindex import InMemoryIndexProvider
    from janusgraph_tpu.indexing.provider import (
        IndexQuery,
        KeyInformation,
        Mapping,
        PredicateCondition,
    )
    from janusgraph_tpu.indexing.remote import (
        RemoteIndexProvider,
        RemoteIndexServer,
    )
    from janusgraph_tpu.core.predicates import Cmp

    backing = InMemoryIndexProvider()
    server = RemoteIndexServer(backing, ledger_echo=ledger_echo).start()
    host, port = server.address
    client = RemoteIndexProvider(hostname=host, port=port)
    info = KeyInformation(str, Mapping.STRING, "SINGLE")
    client.register("store", "name", info)
    client.mutate(
        {"store": {"d1": _mut([("name", "zeus")]),
                   "d2": _mut([("name", "zeus")])}},
        {"store": {"name": info}},
    )
    q = IndexQuery(PredicateCondition("name", Cmp.EQUAL, "zeus"))
    return server, client, q


def _mut(adds):
    from janusgraph_tpu.indexing.provider import IndexEntry, IndexMutation

    m = IndexMutation(is_new=True)
    for f, v in adds:
        m.additions.append(IndexEntry(f, v))
    return m


def test_index_ledger_echo_both_directions():
    # new <-> new: hits measured at the index node, echoed + merged
    server, client, q = _index_fixture()
    try:
        with ledger_scope() as led:
            with tracer.span("idx.client") as root:
                hits = client.query("store", q)
        assert sorted(hits) == ["d1", "d2"]
        assert client._remote_ledger is True
        assert led.get("index_hits") == 2
        trace = _wait_trace(
            root.trace_id,
            lambda t: any(s.name == "index.remote.query" for s in t),
        )
        assert _span_ledger_sum(trace, "index_hits") == 2
    finally:
        client.close()
        server.stop()

    # new client <-> old server: negotiated OFF, local fallback counts
    server, client, q = _index_fixture(ledger_echo=False)
    try:
        with ledger_scope() as led:
            hits = client.query("store", q)
        assert sorted(hits) == ["d1", "d2"]
        assert client._remote_ledger is False
        assert led.get("index_hits") == 2
    finally:
        client.close()
        server.stop()

    # old client <-> new server: byte-compatible, ledger-oblivious
    server, client, q = _index_fixture()
    try:
        from janusgraph_tpu.indexing.remote import RemoteIndexProvider

        old = RemoteIndexProvider(
            hostname=server.address[0], port=server.address[1],
            resource_ledger=False,
        )
        with ledger_scope() as led:
            hits = old.query("store", q)
        assert sorted(hits) == ["d1", "d2"]
        assert led.get("index_hits") == 0
        old.close()
    finally:
        client.close()
        server.stop()


# ----------------------------------------------------------- acceptance
def test_driver_query_ledger_totals_match_span_sums_and_flame():
    """THE acceptance property: one driver query against a
    remote-store-backed server yields a single trace whose ledger totals
    (cells read, bytes moved) equal the sum over its spans' ledger.*
    annotations; the same trace renders to valid collapsed-stack lines
    via `janusgraph_tpu flame <id>`."""
    store_server = RemoteStoreServer(InMemoryStoreManager()).start()
    host, port = store_server.address
    g = open_graph({
        "storage.backend": "remote",
        "storage.hostname": host,
        "storage.port": port,
        "ids.authority-wait-ms": 0.0,
    })
    m = JanusGraphManager()
    m.put_graph("graph", g)
    server = JanusGraphServer(manager=m).start()
    client = JanusGraphClient(port=server.port)
    try:
        tx = g.new_transaction()
        tx.add_vertex(name="costly")
        tx.commit()
        with ledger_scope() as led:
            assert client.submit(
                "g.V().has('name','costly').count()"
            ) == 1
        assert led.get("cells_read") > 0, led.to_dict()
        root = [
            r for r in tracer.recent() if r.name == "driver.submit"
        ][-1]
        trace = _wait_trace(
            root.trace_id,
            lambda t: (
                any(s.name == "server.request" for s in t)
                and _span_ledger_sum(t, "cells_read")
                >= led.get("cells_read")
            ),
        )
        # totals == span sums, for cells and for bytes moved
        for field in ("cells_read", "bytes_read", "cells_written",
                      "bytes_written"):
            assert led.get(field) == _span_ledger_sum(trace, field), field

        # flame export of the same trace: valid collapsed-stack lines
        from janusgraph_tpu.cli import main as cli_main
        import io
        import contextlib

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli_main(["flame", f"{root.trace_id:016x}"])
        assert rc == 0
        lines = [ln for ln in buf.getvalue().splitlines() if ln]
        assert lines
        frame_line = re.compile(r"^[^;\s]+(;[^;\s]+)* \d+$")
        for ln in lines:
            assert frame_line.match(ln), ln
        joined = "\n".join(lines)
        assert "driver.submit" in joined
        assert "server.request" in joined
        # server-side spans fold UNDER the driver root (stitched graft)
        assert any(
            ln.startswith("driver.submit;") and "server.request" in ln
            for ln in lines
        ), lines
        # ledger annotations fold into frame names
        assert "cells_read:" in joined
    finally:
        server.stop()
        g.close()
        store_server.stop()


def test_server_echoes_status_ledger_and_profile_endpoint():
    g = open_graph({"ids.authority-wait-ms": 0.0})
    m = JanusGraphManager()
    m.put_graph("graph", g)
    server = JanusGraphServer(manager=m).start()
    try:
        tx = g.new_transaction()
        tx.add_vertex(name="hera")
        tx.commit()
        body = json.dumps({"gremlin": "g.V().count()"}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/gremlin", data=body,
            method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as resp:
            payload = json.loads(resp.read())
        ledger = payload["status"].get("ledger")
        assert ledger and ledger.get("cells_read", 0) > 0
        # GET /profile serves the digest table, the just-run shape ranked
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/profile"
        ) as resp:
            prof = json.loads(resp.read())
        assert any(
            "full-scan" in d["shape"] for d in prof["digests"]
        ), prof
        # GET /profile/flame of the request's trace -> text lines
        trace_id = payload["status"]["trace"]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/profile/flame?trace={trace_id}"
        ) as resp:
            text = resp.read().decode()
        assert "server.request" in text
    finally:
        server.stop()
        g.close()


# -------------------------------------------------------------- digests
def test_digest_ignores_literals_and_separates_shapes():
    g = open_graph({"ids.authority-wait-ms": 0.0})
    try:
        mgmt = g.management()
        mgmt.make_property_key("uid", int)
        mgmt.build_composite_index("byUid", ["uid"])
        tx = g.new_transaction()
        for i in range(4):
            tx.add_vertex(uid=i)
        tx.commit()
        digest_table.reset()
        src = g.traversal()
        src.V().has("uid", 1).to_list()
        src.V().has("uid", 2).to_list()  # same shape, different literal
        src.V().has("uid", 3).values("uid").to_list()  # extra step
        src.tx.rollback()
        top = digest_table.top(10)
        by_shape = {d["shape"]: d for d in top}
        indexed = [d for d in top if "byUid" in d["shape"]]
        assert indexed, top  # index choice is part of the shape
        same = [d for d in indexed if d["count"] == 2]
        assert same, top  # the two literal-variants share one digest
        assert len(indexed) == 2, top  # count() split into its own shape
        assert all(
            d["digest"] == shape_digest(d["shape"]) for d in top
        )
        assert by_shape  # sanity: table rendered
    finally:
        g.close()


def test_digest_table_bounded_eviction_keeps_heavy_hitters():
    t = __import__(
        "janusgraph_tpu.observability.profiler", fromlist=["DigestTable"]
    ).DigestTable(capacity=3)
    t.observe("aa", "heavy", 100.0)
    for i in range(5):
        t.observe(f"l{i}", f"light{i}", 0.5)
    assert len(t) <= 3
    assert any(d["digest"] == "aa" for d in t.top(10))


def test_profile_returns_resources_block():
    g = open_graph({"ids.authority-wait-ms": 0.0})
    try:
        tx = g.new_transaction()
        tx.add_vertex(name="ares")
        tx.commit()
        metrics = g.traversal().V().has("name", "ares").profile()
        assert metrics.resources.get("cells_read", 0) > 0
        assert metrics.as_dict()["annotations"]["resources"] == (
            metrics.resources
        )
    finally:
        g.close()


def test_slow_span_events_carry_digest():
    from janusgraph_tpu.observability import flight_recorder

    g = open_graph({
        "ids.authority-wait-ms": 0.0,
        "metrics.slow-op-threshold-ms": 0.0001,
    })
    try:
        tx = g.new_transaction()
        tx.add_vertex(name="slowpoke")
        tx.commit()
        g.traversal().V().profile()  # runs under the oltp.traversal span
        slow = [
            e for e in tracer.slow_ops()
            if e["attrs"].get("digest")
        ]
        assert slow, tracer.slow_ops()
        digest = slow[-1]["attrs"]["digest"]
        flights = [
            e for e in flight_recorder.events("slow_span")
            if e.get("digest") == digest
        ]
        assert flights
    finally:
        tracer.configure(slow_threshold_ms=100.0)
        g.close()


def test_traversal_shape_normalization():
    shape = traversal_shape(
        ["adjacentVertexHasId(1, 7)", "has", "out", "count"],
        {"access": "composite-index", "index": "byUid"},
    )
    assert shape == "composite-index[byUid]>adjacentVertexHasId>has>out>count"
    # digits and quoted literals are stripped
    assert traversal_shape(["limit5"], {}) == "traversal>limit"


# -------------------------------------------------------------- roofline
def test_tpu_run_records_report_roofline_via_cost_analysis():
    from janusgraph_tpu.olap.generators import rmat_csr
    from janusgraph_tpu.olap.programs import PageRankProgram
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    csr = rmat_csr(7, 4)
    ex = TPUExecutor(csr)
    with ledger_scope() as led:
        ex.run(PageRankProgram(max_iterations=4, tol=0.0))
    info = ex.last_run_info
    records = info["superstep_records"]
    assert records
    for r in records:
        assert r["flops"] > 0
        assert r["bytes_accessed"] > 0
        assert r["operational_intensity"] > 0
        assert r["roofline_utilization"] is None or (
            r["roofline_utilization"] >= 0
        )
        assert r["cost_source"] == "xla"  # CPU XLA exposes cost_analysis
    assert info["roofline"]["peak_flops"] > 0
    assert "dense" in info["roofline_by_tier"]
    # the record names the device the executor placed its arrays on
    assert (info["platform"], info["device_kind"], info["device_count"]) == (
        "cpu", "cpu", 1
    )
    assert info["resources"]["h2d_bytes"] == info["h2d_arg_bytes"]
    # the run billed its transfer bytes to the ambient ledger
    assert led.get("h2d_bytes") == info["h2d_arg_bytes"]
    assert led.get("d2h_bytes") == info["d2h_bytes"]


def test_tpu_roofline_estimator_fallback(monkeypatch):
    from janusgraph_tpu.observability import profiler
    from janusgraph_tpu.olap.generators import rmat_csr
    from janusgraph_tpu.olap.programs import PageRankProgram
    from janusgraph_tpu.olap.tpu_executor import TPUExecutor

    monkeypatch.setattr(profiler, "harvest_cost", lambda lowered: None)
    csr = rmat_csr(7, 4)
    ex = TPUExecutor(csr)
    ex.run(PageRankProgram(max_iterations=3, tol=0.0))
    records = ex.last_run_info["superstep_records"]
    assert records
    for r in records:
        assert r["cost_source"] == "estimate"
        assert r["flops"] > 0
        assert r["operational_intensity"] > 0


def test_cpu_run_records_report_roofline():
    from janusgraph_tpu.olap.cpu_executor import CPUExecutor
    from janusgraph_tpu.olap.generators import rmat_csr
    from janusgraph_tpu.olap.programs import PageRankProgram

    csr = rmat_csr(6, 4)
    ex = CPUExecutor(csr)
    ex.run(PageRankProgram(max_iterations=3, tol=0.0))
    info = ex.last_run_info
    assert info["path"] == "cpu"
    assert len(info["superstep_records"]) == 3
    for r in info["superstep_records"]:
        assert r["flops"] > 0
        assert r["bytes_accessed"] > 0
        assert r["operational_intensity"] > 0
        assert r["cost_source"] == "estimate"
    assert info["resources"]["flops"] > 0


def test_roofline_peak_config_override():
    from janusgraph_tpu.observability import profiler

    try:
        profiler.configure_roofline(
            peak_flops=1e12, peak_bytes_per_s=1e11
        )
        peaks = profiler.device_peaks("TPU v5 lite")
        assert peaks["peak_flops"] == 1e12
        assert peaks["peak_bytes_per_s"] == 1e11
        assert peaks["source"] == "config"
        point = profiler.roofline_point(1e9, 1e8, 10.0, peaks)
        # oi = 10 flops/byte -> roof = min(1e12, 10 * 1e11) = 1e12;
        # achieved = 1e9 / 0.01s = 1e11 -> utilization 0.1
        assert point["operational_intensity"] == 10.0
        assert abs(point["roofline_utilization"] - 0.1) < 1e-9
    finally:
        profiler.configure_roofline(peak_flops=0.0, peak_bytes_per_s=0.0)


def test_device_peaks_keyed_by_reported_kind():
    """The table is keyed by what the chip reports: a v5e's device_kind is
    "TPU v5 lite", and a kind the table does not list is an error, not a
    default (a utilization against another chip's peak is worse than
    none)."""
    from janusgraph_tpu.observability import profiler

    v5e = profiler.device_peaks("TPU v5 lite")
    assert v5e["peak_bytes_per_s"] == 819e9
    assert v5e["peak_flops"] == 197e12
    assert v5e["source"] == "table:tpu v5 lite"
    assert profiler.device_peaks("cpu")["source"] == "table:cpu"
    for unknown in ("TPU v9", "NVIDIA H100", "tpu"):
        with pytest.raises(KeyError, match="not in the roofline peaks"):
            profiler.device_peaks(unknown)


# ------------------------------------------------------------------- CLI
def test_cli_top_command(capsys):
    from janusgraph_tpu.cli import main as cli_main

    digest_table.reset()
    digest_table.observe("abcd1234", "full-scan>count", 5.0, cells=7)
    assert cli_main(["top", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["digests"][0]["digest"] == "abcd1234"
    assert cli_main(["top"]) == 0
    assert "full-scan>count" in capsys.readouterr().out


def test_cli_flame_unknown_trace_fails():
    from janusgraph_tpu.cli import main as cli_main

    assert cli_main(["flame", "00000000deadbeef"]) == 1


def test_flame_lines_self_time_and_graft():
    from janusgraph_tpu.observability.spans import Span, Tracer

    t = Tracer()
    with t.span("root") as root:
        with t.span("child"):
            time.sleep(0.002)
    # a remote-parented local root grafts under the retained parent
    with t.child_span(root.context(), "remote.op"):
        pass
    lines = flame_lines(t.find_trace(root.trace_id))
    stacks = {ln.rsplit(" ", 1)[0] for ln in lines}
    assert "root" in stacks
    assert "root;child" in stacks
    assert "root;remote.op" in stacks
    for ln in lines:
        assert int(ln.rsplit(" ", 1)[1]) >= 0
