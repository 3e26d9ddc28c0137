"""Config system tests: typed options, mutability levels, KCVS-backed global
config, instance registry (reference: ConfigOption.java mutability semantics,
KCVSConfiguration, StandardJanusGraph instance registration)."""

import pytest

from janusgraph_tpu.core.config import (
    REGISTRY,
    GraphConfiguration,
    Mutability,
    describe_options,
)
from janusgraph_tpu.core.graph import open_graph
from janusgraph_tpu.exceptions import ConfigurationError
from janusgraph_tpu.storage.inmemory import InMemoryStoreManager


def test_unknown_option_rejected():
    with pytest.raises(ConfigurationError, match="unknown configuration"):
        open_graph({"storage.bogus": 1}).close()


def test_type_checked():
    with pytest.raises(ConfigurationError, match="expected int"):
        open_graph({"ids.block-size": "a lot"}).close()


def test_verifier_rejects():
    with pytest.raises(ConfigurationError, match="invalid value"):
        open_graph({"ids.partition-bits": 99}).close()


def test_defaults_apply():
    g = open_graph()
    assert g.config.get("cache.db-cache") is True
    assert g.config.get("ids.partition-bits") == 5
    g.close()


def test_fixed_option_frozen_across_instances():
    mgr = InMemoryStoreManager()
    g1 = open_graph({"ids.partition-bits": 4}, )
    g1.close()
    # same manager: second opener with a conflicting FIXED value fails
    g1 = __import__("janusgraph_tpu.core.graph", fromlist=["JanusGraphTPU"]).JanusGraphTPU(
        {"ids.partition-bits": 4}, store_manager=mgr
    )
    with pytest.raises(ConfigurationError, match="FIXED"):
        __import__("janusgraph_tpu.core.graph", fromlist=["JanusGraphTPU"]).JanusGraphTPU(
            {"ids.partition-bits": 6}, store_manager=mgr
        )
    g1.close()


def test_global_option_set_via_management():
    g = open_graph()
    mgmt = g.management()
    mgmt.set_config("tx.log-tx", True)
    assert g.config.get("tx.log-tx") is True
    g.close()


def test_global_offline_requires_single_instance():
    mgr = InMemoryStoreManager()
    from janusgraph_tpu.core.graph import JanusGraphTPU

    g1 = JanusGraphTPU({}, store_manager=mgr)
    g2 = JanusGraphTPU({}, store_manager=mgr)
    with pytest.raises(ConfigurationError, match="GLOBAL_OFFLINE"):
        g1.management().set_config("ids.block-size", 777)
    g2.close()
    g1.management().set_config("ids.block-size", 777)
    assert g1.config.get("ids.block-size") == 777
    g1.close()


def test_local_option_not_settable_globally():
    g = open_graph()
    with pytest.raises(ConfigurationError, match="LOCAL"):
        g.management().set_config("storage.backend", "other")
    g.close()


def test_instance_registry_and_force_close():
    mgr = InMemoryStoreManager()
    from janusgraph_tpu.core.graph import JanusGraphTPU

    g1 = JanusGraphTPU({}, store_manager=mgr)
    g2 = JanusGraphTPU({}, store_manager=mgr)
    mgmt = g1.management()
    ids = set(mgmt.open_instances())
    assert {g1.instance_id, g2.instance_id} <= ids
    # duplicate registration of a live id fails
    with pytest.raises(ConfigurationError, match="already registered"):
        JanusGraphTPU(
            {"graph.unique-instance-id": g2.instance_id}, store_manager=mgr
        )
    # evict the (simulated stale) second instance
    mgmt.force_close_instance(g2.instance_id)
    assert g2.instance_id not in mgmt.open_instances()
    g1.close()


def test_maskable_local_overrides_stored():
    mgr = InMemoryStoreManager()
    from janusgraph_tpu.core.graph import JanusGraphTPU

    g1 = JanusGraphTPU({}, store_manager=mgr)
    g1.config.set_global("cache.db-cache-size", 1000)
    assert g1.config.get("cache.db-cache-size") == 1000
    g1.close()
    g2 = JanusGraphTPU({"cache.db-cache-size": 2000}, store_manager=mgr)
    assert g2.config.get("cache.db-cache-size") == 2000  # local masks stored
    g2.close()


def test_describe_options_covers_registry():
    doc = describe_options()
    for path in REGISTRY:
        assert path in doc
    assert "global_offline" in doc


def test_mutability_coverage():
    kinds = {o.mutability for o in REGISTRY.values()}
    assert {
        Mutability.LOCAL,
        Mutability.MASKABLE,
        Mutability.GLOBAL,
        Mutability.GLOBAL_OFFLINE,
        Mutability.FIXED,
    } <= kinds


def test_registry_breadth():
    """≥40 registered options (reference has ~140 at
    GraphDatabaseConfiguration.java; the breadth that matters — cache,
    locks, logs, ids, computer, scan — is covered)."""
    from janusgraph_tpu.core.config import REGISTRY

    assert len(REGISTRY) >= 40, sorted(REGISTRY)


def test_computer_options_flow_to_executor():
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({
        "computer.executor": "cpu",
        "computer.ell-max-capacity": 64,
    })
    comp = g.compute()
    assert comp.executor_kind == "cpu"
    # the capacity flows through run_on for tpu executors
    from janusgraph_tpu.olap.computer import run_on
    from janusgraph_tpu.olap import csr_from_edges
    from janusgraph_tpu.olap.programs import PageRankProgram

    csr = csr_from_edges(6, [0, 1, 2], [1, 2, 3])
    out = run_on(csr, PageRankProgram(max_iterations=3),
                 executor="tpu", ell_max_capacity=64)
    assert "rank" in out
    g.close()


def test_scan_options_consumed(tmp_path):
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({"storage.scan-batch-size": 7,
                    "storage.scan-parallelism": 2})
    assert g.config.get("storage.scan-batch-size") == 7
    tx = g.new_transaction()
    for _ in range(5):
        tx.add_vertex()
    tx.commit()
    from janusgraph_tpu.olap.jobs import GhostVertexRemover, run_scan_job

    metrics = run_scan_job(g, GhostVertexRemover(g))
    assert metrics is not None
    g.close()


def test_ids_renew_percentage_reaches_pools():
    from janusgraph_tpu.core.graph import open_graph

    g = open_graph({"ids.renew-percentage": 0.5})
    assert g.id_assigner._relation_pool.RENEW_FRACTION == 0.5
    g.close()
