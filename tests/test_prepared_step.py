"""The host loop's prepared step and the one-transfer start (ISSUE 31).

What is constant per plan shape, the jitted superstep, its argument
pytree and its cost record, is resolved once per
(program.cache_key(), op, channel VALUE, delta signature); a run whose
start is a host-resident seed vector brings only that vector, in one copy.

Contracts under test:
- a second run on one executor is served wholly from prepared steps
  (`olap.executor.prepared_step`) and equals a fresh executor's and the
  CPU oracle's result element for element;
- a prepared step never serves stale arrays: not across `set_delta`
  (another overlay under an EQUAL signature), not after its channel's pack
  left the LRU, not for another channel value under the same name, not for
  a program whose traced body differs;
- the one-transfer start is bit for bit `ones(n) * active * mask`, and a
  view whose `active` is not all ones keeps the product;
- the host loop's other tenants (a phase-alternating combiner, the path
  recorder, the sack) read as they did.
"""

import numpy as np
import pytest

from janusgraph_tpu.observability import registry
from janusgraph_tpu.olap import csr_from_edges
from janusgraph_tpu.olap.cpu_executor import CPUExecutor
from janusgraph_tpu.olap.programs import (
    OLAPTraversalProgram,
    PageRankProgram,
    PeerPressureProgram,
)
from janusgraph_tpu.olap.programs.olap_traversal import TraversalStep
from janusgraph_tpu.olap.tpu_executor import TPUExecutor

N = 300


def _csr(n=N, m=2400, seed=5, weights=False):
    rng = np.random.default_rng(seed)
    return csr_from_edges(
        n, rng.integers(0, n, m).astype(np.int32),
        rng.integers(0, n, m).astype(np.int32),
        weights=rng.integers(1, 4, m).astype(np.float32) if weights else None,
    )


def _steps(*directions):
    return tuple(TraversalStep(d, None, (), None) for d in directions)


def _mask(seed, n=N, k=40):
    """A start vector with multiplicities above 1, as the planner's
    arrival vector has after its host hop."""
    rng = np.random.default_rng(seed)
    mask = np.zeros(n, dtype=np.float32)
    np.add.at(mask, rng.integers(0, n, k), 1.0)
    np.add.at(mask, rng.integers(0, n, k // 4), 3.0)
    assert mask.max() > 1
    return mask


def _prepared():
    return registry.snapshot().get(
        "olap.executor.prepared_step", {}
    ).get("count", 0)


def _same(got, want):
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_array_equal(
            np.asarray(got[key], dtype=np.float64),
            np.asarray(want[key], dtype=np.float64), err_msg=key,
        )


# ------------------------------------------------------- (a) a second run
@pytest.mark.parametrize("directions", [
    ("out",), ("out", "out"), ("out", "in", "both"),
], ids=["one-hop", "two-hops", "three-channels"])
def test_second_run_is_served_from_prepared_steps(directions):
    csr = _csr()
    steps = _steps(*directions)
    ex = TPUExecutor(csr)
    ex.run(OLAPTraversalProgram(steps, seed_mask=_mask(1)))
    assert ex.last_run_info["superstep_records"][0]["compiled"]
    first_bytes = ex.last_run_info["h2d_arg_bytes"]
    first_cost = [
        (r["flops"], r["bytes_accessed"], r["cost_source"])
        for r in ex.last_run_info["superstep_records"]
    ]

    before = _prepared()
    got = ex.run(OLAPTraversalProgram(steps, seed_mask=_mask(2)))
    assert _prepared() - before == len(directions)
    info = ex.last_run_info
    assert info["path"] == "host-loop"
    assert info["supersteps"] == len(directions)
    # the run record reads as if every helper had been asked again
    assert not any(r["compiled"] for r in info["superstep_records"])
    assert info["retraces"] == 0
    assert info["h2d_arg_bytes"] == first_bytes > 0
    assert [
        (r["flops"], r["bytes_accessed"], r["cost_source"])
        for r in info["superstep_records"]
    ] == first_cost

    _same(got, TPUExecutor(csr).run(
        OLAPTraversalProgram(steps, seed_mask=_mask(2))
    ))
    _same(got, CPUExecutor(csr).run(
        OLAPTraversalProgram(steps, seed_mask=_mask(2))
    ))


def test_a_repeated_channel_is_a_hit_within_one_run():
    """A multi-superstep program pays one preparation per (op, channel):
    `out().out()` holds the channel value twice."""
    ex = TPUExecutor(_csr())
    before = _prepared()
    ex.run(OLAPTraversalProgram(_steps("out", "out"), seed_mask=_mask(3)))
    assert _prepared() - before == 1
    assert len(ex._prepared) == 1


# ------------------------------------------------------- (b) never stale
def _fresh(csr, program):
    return TPUExecutor(csr).run(program)


def _stale_other_channel_value(csr):
    # `s0` names ("out", None) in one program and ("in", None) in the next
    ex = TPUExecutor(csr)
    ex.run(OLAPTraversalProgram(_steps("out"), seed_mask=_mask(4)))
    return ex, lambda: OLAPTraversalProgram(_steps("in"), seed_mask=_mask(4))


def _stale_lru_eviction(csr):
    ex = TPUExecutor(csr)
    ex.CHANNEL_CACHE_SIZE = 1
    ex.run(OLAPTraversalProgram(_steps("out"), seed_mask=_mask(4)))
    held = ex._prepared[next(iter(ex._prepared))][1]["hyb"]
    ex.run(OLAPTraversalProgram(_steps("in"), seed_mask=_mask(4)))
    # the evicted pack's prepared step went with it (it held its arrays)
    assert len(ex._channel_packs) == 1 and len(ex._prepared) == 1
    assert ex._prepared[next(iter(ex._prepared))][1]["hyb"] is not held
    return ex, lambda: OLAPTraversalProgram(_steps("out"), seed_mask=_mask(5))


def _stale_step_masks_flipped(csr):
    ex = TPUExecutor(csr)
    ex.run(OLAPTraversalProgram(_steps("out"), seed_mask=_mask(4)))
    keep = (np.arange(N) % 3 != 0).astype(np.float32)[:, None]
    return ex, lambda: OLAPTraversalProgram(
        _steps("out"), seed_mask=_mask(4), step_masks=keep
    )


@pytest.mark.parametrize("arrange", [
    _stale_other_channel_value, _stale_lru_eviction,
    _stale_step_masks_flipped,
], ids=["other-channel-value-same-name", "lru-eviction",
        "has-step-masks-flipped"])
def test_a_prepared_step_is_never_stale(arrange):
    csr = _csr()
    ex, make = arrange(csr)
    before = _prepared()
    got = ex.run(make())
    assert _prepared() == before  # prepared anew, not served
    _same(got, _fresh(csr, make()))
    _same(got, CPUExecutor(csr).run(make()))
    # and from now on it is served, with the same answer
    again = ex.run(make())
    assert _prepared() == before + 1
    _same(again, got)


def _burst(g, vs, seed, adds=3):
    rng = np.random.default_rng(seed)
    tx = g.new_transaction()
    for _ in range(adds):
        a, b = rng.integers(0, len(vs), 2)
        tx.add_edge(
            tx.get_vertex(vs[int(a)].id), "link", tx.get_vertex(vs[int(b)].id)
        )
    tx.commit()


def test_no_prepared_step_survives_set_delta():
    """Two overlays of EQUAL lane signature share the key; their lanes ride
    `gargs["delta"]`, so a step kept across `set_delta` would answer the
    second overlay with the first one's edges."""
    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta as D
    from janusgraph_tpu.olap.csr import load_csr_snapshot

    g = open_graph({"schema.default": "auto", "computer.sharded-auto": False})
    try:
        rng = np.random.default_rng(11)
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(60)]
        for _ in range(240):
            a, b = rng.integers(0, 60, 2)
            tx.add_edge(vs[int(a)], "link", vs[int(b)])
        tx.commit()
        csr, epoch = load_csr_snapshot(g)
        _burst(g, vs, seed=1)
        view1 = D.OverlayView(csr, D.overlay_since(g, epoch)[0])
        _burst(g, vs, seed=2, adds=1)
        view2 = D.OverlayView(csr, D.overlay_since(g, epoch)[0])
        assert view1.sig(False) == view2.sig(False)

        def program():
            return PageRankProgram(max_iterations=3, tol=0.0)

        ex = TPUExecutor(csr)
        base = ex.run(program(), fused=False)
        assert ex._prepared
        for view in (view1, view2, None, view1):
            ex.set_delta(view)
            assert not ex._prepared
            before = _prepared()
            got = ex.run(program(), fused=False)
            assert ex.last_run_info["path"] == "host-loop"
            # the first superstep after a swap is prepared anew
            assert _prepared() - before == 2
            want = (
                TPUExecutor(csr, delta=view).run(program(), fused=False)
                if view is not None else base
            )
            np.testing.assert_array_equal(got["rank"], want["rank"])
        assert not np.array_equal(
            TPUExecutor(csr, delta=view1).run(program(), fused=False)["rank"],
            TPUExecutor(csr, delta=view2).run(program(), fused=False)["rank"],
        )
    finally:
        g.close()


# ------------------------------------------------ (c) the one-transfer start
class _RecordingXp:
    """The array module handed to `setup`, recording what is asked of it."""

    def __init__(self, xp):
        self._xp = xp
        self.asked = []

    def __getattr__(self, name):
        self.asked.append(name)
        return getattr(self._xp, name)


def _todays_start(mask, active, jnp):
    return jnp.ones(len(active)) * active * jnp.asarray(mask)


@pytest.mark.parametrize("mask", [
    _mask(7), _mask(8).astype(np.float64), _mask(9).astype(np.int64),
    (_mask(10) > 0),
], ids=["float32-multiplicities", "float64", "int64", "bool"])
def test_one_transfer_start_is_todays_product_bit_for_bit(mask):
    import jax
    import jax.numpy as jnp

    ex = TPUExecutor(_csr())
    assert ex.g.all_active
    xp = _RecordingXp(jnp)
    state, metrics = OLAPTraversalProgram(
        _steps("out"), seed_mask=mask
    ).setup(ex.g, xp)
    assert metrics == {}
    # one copy and nothing else: no ones, no multiply, no slice, no pad
    assert sorted(xp.asked) == ["asarray", "result_type"]
    count = state["count"]
    assert isinstance(count, jax.Array)
    want = _todays_start(mask, ex.g.active, jnp)
    assert count.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(count), np.asarray(want))


def test_a_start_from_ids_or_all_vertices_keeps_its_setup():
    import jax.numpy as jnp

    ex = TPUExecutor(_csr())
    for kwargs in ({}, {"seed_indices": [3, 5], "seed_mask": _mask(7)}):
        xp = _RecordingXp(jnp)
        OLAPTraversalProgram(_steps("out"), **kwargs).setup(ex.g, xp)
        assert "ones" in xp.asked or "isin" in xp.asked


def test_a_delta_fused_view_still_takes_the_product():
    """The fused view pads past the base rows and zeroes removed ones: its
    `active` is not all ones, so the start is `active * mask`."""
    import jax.numpy as jnp

    from janusgraph_tpu.core.graph import open_graph
    from janusgraph_tpu.olap import delta as D
    from janusgraph_tpu.olap.csr import load_csr_snapshot

    g = open_graph({"schema.default": "auto", "computer.sharded-auto": False})
    try:
        tx = g.new_transaction()
        vs = [tx.add_vertex() for _ in range(20)]
        for i in range(19):
            tx.add_edge(vs[i], "link", vs[i + 1])
        tx.commit()
        csr, epoch = load_csr_snapshot(g)
        tx = g.new_transaction()
        tx.remove_vertex(tx.get_vertex(vs[4].id))
        tx.add_vertex()
        tx.commit()
        view = D.OverlayView(csr, D.overlay_since(g, epoch)[0])
        ex = TPUExecutor(csr, delta=view)
        assert not ex.g.all_active
        n = ex.g.local_num_vertices
        active = np.asarray(ex.g.active)
        assert n > csr.num_vertices and active.min() == 0
        mask = np.full(n, 2.0, dtype=np.float32)
        state, _ = OLAPTraversalProgram(
            _steps("out"), seed_mask=mask
        ).setup(ex.g, jnp)
        np.testing.assert_array_equal(
            np.asarray(state["count"]), active * 2.0
        )
        # back on the base view the executor's view is all-active again
        ex.set_delta(None)
        assert ex.g.all_active
    finally:
        g.close()


def test_a_sharded_executors_padded_shard_still_takes_the_product():
    import jax
    from jax.sharding import Mesh

    from janusgraph_tpu.parallel import ShardedExecutor
    from janusgraph_tpu.parallel.sharded import _GlobalView

    csr = _csr(n=301)  # 301 rows over 8 shards: the last one is padded
    sx = ShardedExecutor(csr, mesh=Mesh(np.array(jax.devices()[:8]), ("p",)))
    steps = _steps("out", "in")
    mask = _mask(12, n=301)
    got = sx.run(OLAPTraversalProgram(steps, seed_mask=mask))
    want = CPUExecutor(csr).run(OLAPTraversalProgram(steps, seed_mask=mask))
    np.testing.assert_array_equal(
        np.asarray(got["count"])[:301], want["count"]
    )
    view = _GlobalView(sx._sharded(False))
    assert view.local_num_vertices > 301 and view.active.min() == 0
    state, _ = OLAPTraversalProgram(steps, seed_mask=mask).setup(view, np)
    count = np.asarray(state["count"])
    np.testing.assert_array_equal(count[:301], mask)
    assert not count[301:].any()


# ------------------------------------- (d) the host loop's other tenants
def _peer_pressure():
    return PeerPressureProgram(num_buckets=64, rounds=4)


def _record_reach():
    return OLAPTraversalProgram(
        _steps("out", "both"), seed_mask=_mask(13), record_reach=True
    )


def _record_reach_from_ids():
    return OLAPTraversalProgram(
        _steps("out", "in"), seed_indices=[1, 2, 3], record_reach=True
    )


def _sack(op):
    return lambda: OLAPTraversalProgram(
        _steps("out", "out"), seed_mask=_mask(14), sack=op
    )


@pytest.mark.parametrize("make", [
    _peer_pressure, _record_reach, _record_reach_from_ids,
    _sack("sum"), _sack("mult"),
], ids=["peer-pressure", "record-reach", "record-reach-from-ids",
        "sack-sum", "sack-mult"])
def test_other_host_loop_programs_read_as_before(make):
    csr = _csr(weights=True)
    ex = TPUExecutor(csr)
    first = ex.run(make())
    assert ex.last_run_info["path"] == "host-loop"
    steps = ex.last_run_info["supersteps"]
    variants = len(ex._prepared)
    # a phase-alternating combiner prepares one step per (op, channel)
    assert variants == (2 if make is _peer_pressure else len(
        set(make().edge_channels.values())
    ))
    before = _prepared()
    second = ex.run(make())
    assert _prepared() - before == steps
    _same(second, first)
    _same(first, _fresh(csr, make()))
    cpu = CPUExecutor(csr).run(make())
    for key in cpu:
        np.testing.assert_allclose(
            np.asarray(first[key], dtype=np.float64), cpu[key],
            rtol=1e-5, atol=1e-6, err_msg=key,
        )
