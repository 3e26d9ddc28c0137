"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding paths
(Mesh/shard_map over partitions) are exercised without TPU hardware — the
"multi-node without a cluster" technique, mirroring the reference's pattern
of opening several store managers against one backend in a single JVM
(reference: janusgraph-backend-testutils .../IDAuthorityTest.java,
LogTest.java).
"""

import os

# Must be set before jax is first imported. Forced (not setdefault): on a
# machine with a chip JAX would otherwise take it, but the suite needs the
# 8-virtual-device CPU mesh (and must leave the chip to whoever holds it).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

from janusgraph_tpu.storage.inmemory import InMemoryStoreManager  # noqa: E402


def _make_backend(kind: str, tmp_path):
    if kind == "inmemory":
        return InMemoryStoreManager()
    if kind == "local":
        from janusgraph_tpu.storage.localstore import open_local_kcvs

        return open_local_kcvs(str(tmp_path / "localstore"), fsync=False)
    if kind == "sharded":
        from janusgraph_tpu.storage.sharded_store import ShardedStoreManager

        return ShardedStoreManager(num_nodes=3)
    if kind == "ttl":
        from janusgraph_tpu.storage.ttl import TTLStoreManager

        # ttl=0 (never expires): exercises the value framing transparently
        return TTLStoreManager(InMemoryStoreManager(), default_ttl_seconds=0.0)
    if kind == "remote":
        # a REAL networked backend: every store op crosses a TCP socket to
        # an in-process server (the cql/hbase-analogue adapter)
        from janusgraph_tpu.storage.remote import (
            RemoteStoreManager,
            RemoteStoreServer,
        )

        server = RemoteStoreServer(InMemoryStoreManager()).start()
        host, port = server.address
        mgr = RemoteStoreManager(host, port)
        orig_close = mgr.close

        def close_with_server():
            orig_close()
            server.stop()

        mgr.close = close_with_server
        return mgr
    raise ValueError(kind)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soak/stress cases excluded from tier-1 "
        "(-m 'not slow')",
    )


@pytest.fixture(params=["inmemory", "local", "sharded", "ttl", "remote"])
def store_manager(request, tmp_path):
    """Parameterization point for backend-contract suites: every backend
    must pass the same abstract suites (the reference's
    backend-testutils pattern: abstract suites subclassed per backend)."""
    mgr = _make_backend(request.param, tmp_path)
    yield mgr
    mgr.close()
