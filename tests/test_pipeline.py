"""Pipelined async wire framing (ISSUE 11, storage/pipeline.py).

Covers the tentpole contract: old/new byte-compat across every feature-
bit combination (trace x ledger x deadline x pipeline), out-of-order
completion on one connection, coalescing (merged multi-gets and batched
mutates, demuxed per op), per-op deadline expiry mid-pipeline, fault
injection mid-pipeline (breaker counts the failed op only, siblings
complete), the adaptive sync/pipelined routing gate, the driver's WS
multiplexing, and a threaded e2e throughput acceptance run against a
latency-simulated storage node.
"""

import socket
import struct
import threading
import time

import pytest

from janusgraph_tpu.exceptions import (
    DeadlineExceededError,
    PermanentBackendError,
    TemporaryBackendError,
)
from janusgraph_tpu.storage.inmemory import InMemoryStoreManager
from janusgraph_tpu.storage.kcvs import KeySliceQuery, SliceQuery
from janusgraph_tpu.storage.pipeline import PIPELINE_FLAG, WireOp
from janusgraph_tpu.storage.remote import (
    _OP_BATCH,
    _OP_GET_SLICE,
    RemoteStoreManager,
    RemoteStoreServer,
)


def _force_pipeline(mgr):
    """Bypass the adaptive gate: route every eligible op pipelined."""
    mgr._should_pipeline = lambda: True
    return mgr


class _HookStore:
    """Store wrapper calling a hook before every read (blocking /
    failing / latency faults at the serving node)."""

    def __init__(self, inner, hook):
        self._inner = inner
        self._hook = hook

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def get_slice(self, query, txh):
        self._hook(query.key)
        return self._inner.get_slice(query, txh)

    def get_slice_multi(self, keys, sq, txh):
        self._hook(keys[0] if keys else b"")
        return self._inner.get_slice_multi(keys, sq, txh)

    def mutate(self, key, adds, dels, txh):
        self._hook(key)
        return self._inner.mutate(key, adds, dels, txh)


class _HookManager:
    def __init__(self, inner, hook):
        self._inner = inner
        self._hook = hook

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def open_database(self, name):
        return _HookStore(self._inner.open_database(name), self._hook)


def _gs_body(store: str, key: bytes) -> bytes:
    out = []
    sb = store.encode()
    out.append(struct.pack(">I", len(sb)) + sb)
    out.append(struct.pack(">I", len(key)) + key)
    out.append(struct.pack(">I", 0) + struct.pack(">I", 0)
               + struct.pack(">i", -1))
    return b"".join(out)


def _recv_frame(sock):
    head = b""
    while len(head) < 5:
        head += sock.recv(5 - len(head))
    (blen,) = struct.unpack(">I", head[:4])
    payload = b""
    while len(payload) < blen:
        payload += sock.recv(blen - len(payload))
    return head[4], payload


# ----------------------------------------------------------- basic contract
def test_negotiation_and_pipelined_roundtrip():
    server = RemoteStoreServer(InMemoryStoreManager()).start()
    mgr = _force_pipeline(RemoteStoreManager(*server.address))
    try:
        store = mgr.open_database("edgestore")
        store.mutate(b"k", [(b"a", b"1")], [], None)
        assert mgr._remote_pipeline is True
        got = store.get_slice(KeySliceQuery(b"k", SliceQuery(b"", None)), None)
        assert got == [(b"a", b"1")]
        # the ops actually rode pipelined frames
        assert mgr._mux is not None and mgr._mux._conns[0]._epoch is not None
        from janusgraph_tpu.observability import registry

        mgr._mux.flush_stats()
        snap = registry.snapshot()
        assert snap.get("storage.remote.pipeline.ops", {}).get("count", 0) >= 2
    finally:
        mgr.close()
        server.stop()


@pytest.mark.parametrize("trace", [True, False])
@pytest.mark.parametrize("ledger", [True, False])
@pytest.mark.parametrize("deadline", [True, False])
@pytest.mark.parametrize("pipeline", [True, False])
def test_wire_compat_matrix(trace, ledger, deadline, pipeline):
    """New client against every server feature-bit combination: the op
    stream stays byte-compatible, the client negotiates each capability
    independently, and un-negotiated bits are never sent."""
    from janusgraph_tpu.core.deadline import deadline_scope
    from janusgraph_tpu.observability import tracer
    from janusgraph_tpu.observability.profiler import ledger_scope

    server = RemoteStoreServer(
        InMemoryStoreManager(), trace_propagation=trace, ledger_echo=ledger,
        deadline_propagation=deadline, pipeline=pipeline,
    ).start()
    mgr = _force_pipeline(RemoteStoreManager(*server.address))
    try:
        store = mgr.open_database("edgestore")
        with tracer.span("compat.root"):
            with ledger_scope():
                with deadline_scope(5_000.0):
                    store.mutate(b"k", [(b"a", b"1")], [], None)
                    got = store.get_slice(
                        KeySliceQuery(b"k", SliceQuery(b"", None)), None
                    )
        assert got == [(b"a", b"1")]
        assert mgr._remote_trace is trace
        assert mgr._remote_ledger is ledger
        assert mgr._remote_deadline is deadline
        assert mgr._remote_pipeline is pipeline
    finally:
        mgr.close()
        server.stop()


def test_old_client_against_new_server():
    """The other direction: a pipeline-disabled client (byte-identical
    frames to a pre-pipeline client) interoperates with a new server."""
    server = RemoteStoreServer(InMemoryStoreManager()).start()
    mgr = RemoteStoreManager(*server.address, pipeline=False)
    try:
        store = mgr.open_database("edgestore")
        store.mutate(b"k", [(b"a", b"1")], [], None)
        got = store.get_slice(KeySliceQuery(b"k", SliceQuery(b"", None)), None)
        assert got == [(b"a", b"1")]
        assert mgr._mux is None  # the mux never engaged
    finally:
        mgr.close()
        server.stop()


def test_pipelined_frame_against_old_server_is_unknown_op():
    """A 0x10-flagged frame against a pipeline=False server behaves
    byte-identically to a real old server: unknown op, permanent."""
    server = RemoteStoreServer(InMemoryStoreManager(), pipeline=False).start()
    sock = socket.create_connection(server.address)
    try:
        body = struct.pack(">I", 1) + _gs_body("edgestore", b"k")
        sock.sendall(
            struct.pack(">IB", len(body), _OP_GET_SLICE | PIPELINE_FLAG)
            + body
        )
        status, payload = _recv_frame(sock)
        assert status == 2  # permanent, unflagged (old framing)
        assert b"unknown op" in payload
    finally:
        sock.close()
        server.stop()


# ------------------------------------------------- out-of-order completion
def test_out_of_order_completion_on_one_connection():
    """A batch carrier's sub-ops complete out of order: the fast op's
    response (by request id) arrives while the slow sibling is still
    blocked server-side."""
    release = threading.Event()
    entered = threading.Event()

    def hook(key):
        if key == b"slow":
            entered.set()
            assert release.wait(5.0)

    backing = _HookManager(InMemoryStoreManager(), hook)
    server = RemoteStoreServer(backing, pipeline_workers=4).start()
    sock = socket.create_connection(server.address)
    try:
        subs = []
        for rid, key in ((1, b"slow"), (2, b"fast")):
            sub_body = struct.pack(">I", rid) + _gs_body("edgestore", key)
            subs.append(
                struct.pack(
                    ">IB", len(sub_body), _OP_GET_SLICE | PIPELINE_FLAG
                ) + sub_body
            )
        body = struct.pack(">I", len(subs)) + b"".join(subs)
        sock.sendall(
            struct.pack(">IB", len(body), _OP_BATCH | PIPELINE_FLAG) + body
        )
        status, payload = _recv_frame(sock)
        assert status & PIPELINE_FLAG
        (rid,) = struct.unpack_from(">I", payload, 0)
        assert rid == 2, "fast op must complete before the blocked one"
        assert entered.is_set()
        release.set()
        status, payload = _recv_frame(sock)
        (rid,) = struct.unpack_from(">I", payload, 0)
        assert rid == 1
    finally:
        release.set()
        sock.close()
        server.stop()


# ----------------------------------------------------------------- merging
def test_coalesced_ops_merge_and_demux_per_op():
    """Same-slice getSlice ops queued together merge into ONE
    getSliceMulti wire frame; each caller still gets exactly its own
    key's entries. Mutates merge into one mutateMany."""
    server = RemoteStoreServer(InMemoryStoreManager()).start()
    mgr = _force_pipeline(RemoteStoreManager(*server.address))
    try:
        store = mgr.open_database("edgestore")
        for i in range(6):
            store.mutate(f"k{i}".encode(), [(b"c", str(i).encode())], [], None)
        mux = mgr._mux
        conn = mux._conns[0]
        ep = conn._epoch
        # build a queued batch by hand and encode it: deterministic merge
        from janusgraph_tpu.storage.pipeline import OpFuture, _Entry

        sl = struct.pack(">I", 0) + struct.pack(">I", 0) + struct.pack(">i", -1)
        entries = []
        for i in range(4):
            key = f"k{i}".encode()
            body = _gs_body("edgestore", key)
            item = WireOp(
                _OP_GET_SLICE, 0, b"", body,
                merge=("gs", "edgestore", key, sl),
            )
            e = _Entry(item, OpFuture())
            entries.append(e)
        buf, nops = conn._encode_batch(ep, entries)
        assert nops == 4
        # ONE wire frame, not a carrier of four: the merged multi
        raw_op = buf[4]
        assert raw_op & ~0xF0 == 3  # _OP_GET_SLICE_MULTI
        ep.sock.sendall(buf)
        deadline = time.monotonic() + 5.0
        while any(not e.fut.done() for e in entries):
            assert time.monotonic() < deadline
            conn._recv_one(ep)  # drive the receive loop ourselves
        for i, e in enumerate(entries):
            payload, fields = e.fut.result(1.0)
            from janusgraph_tpu.storage.remote import _Reader, _decode_entries

            got = _decode_entries(_Reader(payload))
            assert got == [(b"c", str(i).encode())]
            assert fields is None  # merged ops count client-side
        from janusgraph_tpu.observability import registry

        mgr._mux.flush_stats()
        snap = registry.snapshot()
        assert snap["storage.remote.pipeline.merged_ops"]["count"] >= 4
    finally:
        mgr.close()
        server.stop()


def test_threaded_pipelined_correctness_and_coalescing():
    """16 threads of mixed reads/writes over the pipelined path: every
    op's result is exact, and the wire carried fewer frames than ops
    (coalescing engaged)."""
    server = RemoteStoreServer(InMemoryStoreManager()).start()
    mgr = _force_pipeline(RemoteStoreManager(*server.address))
    errs = []

    def worker(i):
        try:
            store = mgr.open_database("edgestore")
            for j in range(40):
                k = f"w{i}-{j:02d}".encode()
                store.mutate(k, [(b"c", str(j).encode())], [], None)
                got = store.get_slice(
                    KeySliceQuery(k, SliceQuery(b"", None)), None
                )
                assert got == [(b"c", str(j).encode())], got
        except Exception as e:  # noqa: BLE001 - surfaced via errs
            errs.append(e)

    try:
        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(16)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs == []
        from janusgraph_tpu.observability import registry

        mgr._mux.flush_stats()
        snap = registry.snapshot()
        ops = snap["storage.remote.pipeline.ops"]["count"]
        frames = snap["storage.remote.pipeline.wire_frames"]["count"]
        assert ops >= 16 * 40 * 2
        assert frames <= ops  # never more frames than ops
    finally:
        mgr.close()
        server.stop()


# ------------------------------------------------------------- deadlines
def test_per_op_deadline_expiry_mid_pipeline():
    """An op whose budget is spent while a slow sibling holds the
    server's (single) pipeline worker is refused by the server with a
    permanent deadline error — and the sibling completes fine."""
    release = threading.Event()

    def hook(key):
        if key == b"slow":
            assert release.wait(5.0)

    backing = _HookManager(InMemoryStoreManager(), hook)
    server = RemoteStoreServer(backing, pipeline_workers=1).start()
    sock = socket.create_connection(server.address)
    try:
        from janusgraph_tpu.storage.remote import (
            _DEADLINE_FLAG,
            encode_deadline_prefix,
        )

        subs = []
        sub1 = struct.pack(">I", 1) + _gs_body("edgestore", b"slow")
        subs.append(struct.pack(
            ">IB", len(sub1), _OP_GET_SLICE | PIPELINE_FLAG) + sub1)
        # 80 ms budget, queued behind a ~300 ms sibling
        sub2 = (struct.pack(">I", 2) + encode_deadline_prefix(80.0)
                + _gs_body("edgestore", b"fast"))
        subs.append(struct.pack(
            ">IB", len(sub2),
            _OP_GET_SLICE | _DEADLINE_FLAG | PIPELINE_FLAG) + sub2)
        body = struct.pack(">I", 2) + b"".join(subs)
        sock.sendall(
            struct.pack(">IB", len(body), _OP_BATCH | PIPELINE_FLAG) + body
        )
        time.sleep(0.3)
        release.set()
        replies = {}
        for _ in range(2):
            status, payload = _recv_frame(sock)
            (rid,) = struct.unpack_from(">I", payload, 0)
            replies[rid] = (status & 0x0F, payload[4:])
        assert replies[1][0] == 0  # the slow sibling completed OK
        assert replies[2][0] == 2  # permanent: never replayed
        assert b"Deadline" in replies[2][1] or b"deadline" in replies[2][1]
    finally:
        release.set()
        sock.close()
        server.stop()


def test_deadline_expired_in_send_queue_client_side():
    """An op whose deadline lapses before the pipelined send is refused
    client-side (counter + DeadlineExceededError), no wire dispatch."""
    server = RemoteStoreServer(InMemoryStoreManager()).start()
    mgr = _force_pipeline(RemoteStoreManager(*server.address))
    try:
        mux = mgr._mux_for(_OP_GET_SLICE)
        from janusgraph_tpu.storage.remote import _DEADLINE_FLAG

        item = WireOp(
            _OP_GET_SLICE, _DEADLINE_FLAG, b"",
            _gs_body("edgestore", b"k"),
            expires_at=time.monotonic() - 0.001,
        )
        fut = mux.submit(item)
        with pytest.raises(DeadlineExceededError):
            fut.result(2.0)
    finally:
        mgr.close()
        server.stop()


# ------------------------------------------------------ faults and breaker
def test_fault_mid_pipeline_fails_only_its_op_and_breaker_counts_one():
    """A serving-node fault on one in-flight op: the sibling completes,
    the failed op surfaces its own error, and the client breaker counts
    exactly that op (stays CLOSED below threshold)."""
    from janusgraph_tpu.storage.circuit import CLOSED, OPEN

    def hook(key):
        if key == b"bad":
            raise TemporaryBackendError("injected serving-node fault")

    backing = _HookManager(InMemoryStoreManager(), hook)
    server = RemoteStoreServer(backing).start()
    mgr = _force_pipeline(RemoteStoreManager(
        *server.address, max_attempts=1, retry_time_s=0.2,
        breaker_enabled=True, breaker_failure_threshold=2,
        breaker_reset_ms=10_000.0,
    ))
    try:
        store = mgr.open_database("edgestore")
        store.mutate(b"good", [(b"a", b"1")], [], None)
        results = {}

        def read(key):
            try:
                results[key] = store.get_slice(
                    KeySliceQuery(key, SliceQuery(b"", None)), None
                )
            except Exception as e:  # noqa: BLE001 - asserted below
                results[key] = e

        threads = [
            threading.Thread(target=read, args=(k,))
            for k in (b"good", b"bad", b"good")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results[b"good"] == [(b"a", b"1")]
        assert isinstance(results[b"bad"], TemporaryBackendError)
        # ONE failed op = AT MOST one breaker failure (threshold 2 not
        # reached): the carrier frame did not multiply the event
        assert mgr.breaker.state == CLOSED
        # consecutive bad ops trip it: per-op accounting, not per-frame
        from janusgraph_tpu.exceptions import CircuitOpenError

        for _ in range(2):
            with pytest.raises(
                (TemporaryBackendError, CircuitOpenError, PermanentBackendError)
            ):
                store.get_slice(
                    KeySliceQuery(b"bad", SliceQuery(b"", None)), None
                )
        assert mgr.breaker.state == OPEN
    finally:
        mgr.close()
        server.stop()


def test_connection_loss_fails_inflight_and_recovers():
    """Killing the server fails every in-flight pipelined op with a
    temporary error; the retry guard replays against the restarted
    server over a fresh epoch."""
    backing = InMemoryStoreManager()
    server = RemoteStoreServer(backing).start()
    host, port = server.address
    mgr = _force_pipeline(RemoteStoreManager(host, port, retry_time_s=8.0))
    try:
        store = mgr.open_database("edgestore")
        store.mutate(b"k", [(b"a", b"1")], [], None)
        server.stop()

        def restart():
            time.sleep(0.4)
            RemoteStoreServer(backing, host=host, port=port).start()

        threading.Thread(target=restart, daemon=True).start()
        got = store.get_slice(KeySliceQuery(b"k", SliceQuery(b"", None)), None)
        assert got == [(b"a", b"1")]
    finally:
        mgr.close()


# ---------------------------------------------------------- adaptive gate
def test_adaptive_gate_keeps_sequential_callers_on_sync_path():
    """A sequential caller never engages the mux (zero extra cost), and
    a fast backend stays sync even under concurrency."""
    server = RemoteStoreServer(InMemoryStoreManager()).start()
    mgr = RemoteStoreManager(*server.address)
    try:
        store = mgr.open_database("edgestore")
        for i in range(20):
            store.mutate(f"k{i}".encode(), [(b"a", b"1")], [], None)
        assert mgr._mux is None  # never engaged
        assert not mgr._should_pipeline()
    finally:
        mgr.close()
        server.stop()


def test_adaptive_gate_engages_on_latency_dominated_concurrency():
    def hook(_key):
        time.sleep(0.002)

    backing = _HookManager(InMemoryStoreManager(), hook)
    server = RemoteStoreServer(backing, pipeline_workers=16).start()
    mgr = RemoteStoreManager(*server.address)
    try:
        store = mgr.open_database("edgestore")

        def worker(i):
            for j in range(8):
                store.mutate(f"g{i}-{j}".encode(), [(b"a", b"1")], [], None)
                store.get_slice(
                    KeySliceQuery(f"g{i}-{j}".encode(), SliceQuery(b"", None)),
                    None,
                )

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(12)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert mgr._op_ewma_s > mgr._PIPELINE_LATENCY_GATE_S
        from janusgraph_tpu.observability import registry

        if mgr._mux is not None:
            mgr._mux.flush_stats()
        snap = registry.snapshot()
        assert snap.get(
            "storage.remote.pipeline.ops", {}
        ).get("count", 0) > 0, "mux should have engaged under latency"
    finally:
        mgr.close()
        server.stop()


# -------------------------------------------------- observability plumbing
def test_trace_and_ledger_attribute_to_individual_pipelined_ops():
    from janusgraph_tpu.observability import tracer
    from janusgraph_tpu.observability.profiler import ledger_scope

    server = RemoteStoreServer(InMemoryStoreManager()).start()
    mgr = _force_pipeline(RemoteStoreManager(*server.address))
    try:
        store = mgr.open_database("edgestore")
        with tracer.span("pipe.root") as root:
            with ledger_scope() as led:
                store.mutate(b"k", [(b"a", b"12345")], [], None)
                store.get_slice(
                    KeySliceQuery(b"k", SliceQuery(b"", None)), None
                )
        assert led.to_dict().get("cells_read", 0) >= 1  # echo merged
        deadline = time.monotonic() + 2.0
        names = set()
        while time.monotonic() < deadline:
            names = {
                s.name for s in tracer.find_trace(root.trace_id)
                if s.name.startswith("store.remote.")
            }
            if len(names) >= 2:
                break
            time.sleep(0.01)
        assert {"store.remote.mutate", "store.remote.getSlice"} <= names
    finally:
        mgr.close()
        server.stop()


def test_healthz_pipeline_block():
    server = RemoteStoreServer(InMemoryStoreManager()).start()
    mgr = _force_pipeline(RemoteStoreManager(*server.address))
    try:
        store = mgr.open_database("edgestore")
        store.mutate(b"k", [(b"a", b"1")], [], None)
        from janusgraph_tpu.server.server import healthz_snapshot

        mgr._mux.flush_stats()
        block = healthz_snapshot()["pipeline"]
        assert "storage.remote" in block
        entry = block["storage.remote"]
        assert entry["ops"] >= 1
        assert entry["wire_frames"] >= 1
        assert "coalesce_ratio" in entry
        assert "in_flight" in entry
    finally:
        mgr.close()
        server.stop()


def test_negotiation_fallback_flight_event():
    from janusgraph_tpu.observability import flight_recorder

    flight_recorder.reset()
    server = RemoteStoreServer(InMemoryStoreManager(), pipeline=False).start()
    mgr = RemoteStoreManager(*server.address)
    mgr._should_pipeline = lambda: True  # want pipelining; server refuses
    try:
        store = mgr.open_database("edgestore")
        store.mutate(b"k", [(b"a", b"1")], [], None)
        events = flight_recorder.events("pipeline_fallback")
        assert events and events[0]["protocol"] == "storage.remote"
    finally:
        mgr.close()
        server.stop()


# ------------------------------------------------------------ index tier
def test_index_pipelined_queries_and_capability_byte():
    from janusgraph_tpu.indexing.memindex import InMemoryIndexProvider
    from janusgraph_tpu.indexing.provider import (
        IndexQuery,
        KeyInformation,
        Mapping,
        PredicateCondition,
    )
    from janusgraph_tpu.core.predicates import predicate_by_name
    from janusgraph_tpu.indexing.remote import (
        RemoteIndexProvider,
        RemoteIndexServer,
    )

    server = RemoteIndexServer(InMemoryIndexProvider()).start()
    host, port = server.address
    client = RemoteIndexProvider(hostname=host, port=port)
    client._should_pipeline = lambda: True
    try:
        info = KeyInformation(str, Mapping.STRING, "SINGLE")
        client.register("vidx", "name", info)
        from janusgraph_tpu.indexing.provider import IndexEntry, IndexMutation

        m = IndexMutation(is_new=True)
        m.additions.append(IndexEntry("name", "hercules"))
        client.mutate({"vidx": {"d1": m}}, {"vidx": {"name": info}})
        assert client._remote_pipeline is True
        q = IndexQuery(
            PredicateCondition("name", predicate_by_name("eq"), "hercules")
        )
        hits = client.query("vidx", q)
        assert hits == ["d1"]
        from janusgraph_tpu.observability import registry

        client._mux.flush_stats()
        snap = registry.snapshot()
        assert snap.get(
            "index.remote.pipeline.ops", {}
        ).get("count", 0) >= 1
    finally:
        client.close()
        server.stop()


def test_index_old_featured_server_negotiates_pipeline_off():
    from janusgraph_tpu.indexing.memindex import InMemoryIndexProvider
    from janusgraph_tpu.indexing.remote import (
        RemoteIndexProvider,
        RemoteIndexServer,
    )

    server = RemoteIndexServer(
        InMemoryIndexProvider(), pipeline=False
    ).start()
    host, port = server.address
    client = RemoteIndexProvider(hostname=host, port=port)
    try:
        client.features()
        assert client._remote_pipeline is False
        assert client.exists() in (True, False)  # plain op unaffected
    finally:
        client.close()
        server.stop()


# -------------------------------------------------------- driver WS mux
def test_ws_multiplexed_submits_share_one_socket():
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.driver import JanusGraphClient
    from janusgraph_tpu.server import JanusGraphManager, JanusGraphServer

    graph = open_graph({"storage.backend": "inmemory"})
    tx = graph.new_transaction()
    ids = [tx.add_vertex(name=f"v{i}").id for i in range(8)]
    tx.commit()
    manager = JanusGraphManager()
    manager.put_graph("graph", graph)
    server = JanusGraphServer(manager=manager, admission_enabled=False).start()
    try:
        client = JanusGraphClient(port=server.port)
        ws = client.ws(multiplex=True)
        results = {}
        errs = []

        def worker(i):
            try:
                results[i] = ws.submit(f"g.V({ids[i]}).values('name')")
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs == []
        for i in range(8):
            assert results[i] == [f"v{i}"]
        ws.close()
        # serial (non-multiplexed) session still works against the same
        # server — the old driver behavior
        ws2 = client.ws(multiplex=False)
        assert ws2.submit(f"g.V({ids[0]}).values('name')") == ["v0"]
        ws2.close()
    finally:
        server.stop()
        graph.close()


# ------------------------------------------------- e2e protocol acceptance
def test_threaded_e2e_pipelined_beats_sync_under_storage_latency():
    """The acceptance shape: against a storage node with real (simulated
    2 ms) per-op service time and the DEFAULT connection budgets, many
    in-flight ops share few sockets instead of convoying on the pool.
    What the protocol does is asserted, not how long this host took: the
    synchronous framing puts every operation in a frame of its own on a
    pooled socket, so the node never serves more operations at once than
    the pool has sockets; the pipelined framing carries the same
    operations in no more frames than operations, over fewer sockets, and
    the node serves several times the pool's width at once."""
    from janusgraph_tpu.observability import registry

    counters = ("ops", "wire_frames", "merged_ops")

    def moved(before):
        snap = registry.snapshot()
        return {
            c: snap.get(f"storage.remote.pipeline.{c}", {}).get("count", 0)
            - before.get(c, 0)
            for c in counters
        }

    def run(pipeline):
        depth = {"now": 0, "peak": 0}
        depth_lock = threading.Lock()

        def hook(_key):
            with depth_lock:
                depth["now"] += 1
                depth["peak"] = max(depth["peak"], depth["now"])
            time.sleep(0.002)
            with depth_lock:
                depth["now"] -= 1

        backing = _HookManager(InMemoryStoreManager(), hook)
        server = RemoteStoreServer(backing, pipeline_workers=48).start()
        mgr = RemoteStoreManager(*server.address, pipeline=pipeline)
        store = mgr.open_database("edgestore")
        before = moved({})
        errs = []

        def worker(i):
            try:
                for j in range(10):
                    k = f"t{i}-{j}".encode()
                    store.mutate(k, [(b"c", b"v")], [], None)
                    got = store.get_slice(
                        KeySliceQuery(k, SliceQuery(b"", None)), None
                    )
                    assert got == [(b"c", b"v")]
            except Exception as e:  # noqa: BLE001 - surfaced below
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(24)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        if mgr._mux is not None:
            mgr._mux.flush_stats()
        sockets = len(mgr._pool), mgr.pipeline_connections
        mgr.close()
        server.stop()
        assert errs == []
        return moved(before), depth["peak"], sockets

    total_ops = 24 * 10 * 2
    sync, sync_peak, (pool, _) = run(False)
    # one frame an operation, none of them over the pipelined framing
    assert sync == {"ops": 0, "wire_frames": 0, "merged_ops": 0}
    assert 1 <= sync_peak <= pool

    pipe, pipe_peak, (pool, conns) = run(True)
    # the adaptive gate lets the first few operations ride the pool while
    # it learns the service time; the rest share the pipelined sockets
    assert total_ops // 2 <= pipe["ops"] <= total_ops
    assert 1 <= pipe["wire_frames"] <= pipe["ops"]
    assert 0 <= pipe["merged_ops"] <= pipe["ops"]
    assert conns < pool
    assert pipe_peak >= 2 * pool, (
        f"peak in-flight depth {pipe_peak} pipelined over {conns} sockets "
        f"vs {sync_peak} synchronous over {pool}"
    )
