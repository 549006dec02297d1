"""Array-native compile front-end: cross-validation of the wave-based
partitioner, the dense batched FCFS order constructor, the OrderBatch
projection path, the shape-bucket compile cache, and the comm-guided
mutation (PR 4)."""

import dataclasses

import numpy as np
import pytest

from repro.core import (
    DYNAP_SE,
    ExecutionTrace,
    OrderBatch,
    SelfTimedExecutor,
    batch_execute,
    bind_ours,
    build_app,
    build_static_orders,
    build_static_orders_batch,
    compile_cache_stats,
    mcr_batch,
    mcr_howard,
    optimize_binding,
    order_cycle_lower_bounds,
    partition_greedy,
    partition_greedy_reference,
    project_order,
    project_order_batch,
    reset_compile_cache_stats,
    sdfg_from_clusters,
    single_tile_order,
    small_app,
    stack_hardware_aware,
)
from repro.core.hardware import HardwareConfig
from repro.core.optimize import _comm_guided_mutate
from repro.core.partition import ClusteredSNN
from repro.core.sdfg import hardware_aware_sdfg
from tests._hypothesis_compat import given, settings, st


# ======================================================================
# wave-based partitioner vs the scalar reference
# ======================================================================
@settings(max_examples=10, deadline=None)
@given(
    st.integers(min_value=40, max_value=320),
    st.integers(min_value=200, max_value=4500),
    st.integers(min_value=0, max_value=1000),
)
def test_wave_partitioner_bit_identical_randomized(n_neurons, n_synapses, seed):
    snn = small_app(n_neurons, n_synapses, seed=seed)
    wave = partition_greedy(snn, DYNAP_SE)
    ref = partition_greedy_reference(snn, DYNAP_SE)
    assert wave.n_clusters == ref.n_clusters
    np.testing.assert_array_equal(wave.cluster_of, ref.cluster_of)
    np.testing.assert_array_equal(wave.inputs_used, ref.inputs_used)
    np.testing.assert_array_equal(wave.synapses_used, ref.synapses_used)
    np.testing.assert_allclose(wave.out_spikes, ref.out_spikes)


@pytest.mark.parametrize("name", ["MLP-MNIST", "CNN-MNIST"])
def test_wave_partitioner_bit_identical_table1(name):
    snn = build_app(name)
    wave = partition_greedy(snn, DYNAP_SE)
    ref = partition_greedy_reference(snn, DYNAP_SE)
    np.testing.assert_array_equal(wave.cluster_of, ref.cluster_of)
    # feasibility is re-checked by check_clustering inside both calls;
    # utilization must therefore agree exactly too
    assert wave.utilization(DYNAP_SE.tile.crossbar) == ref.utilization(
        DYNAP_SE.tile.crossbar
    )


def test_wave_partitioner_small_crossbar():
    """Non-default crossbar geometry exercises different probe dynamics."""
    from repro.core.hardware import CrossbarConfig, TileConfig

    hw = dataclasses.replace(
        DYNAP_SE, tile=TileConfig(crossbar=CrossbarConfig(64, 64, 64 * 64))
    )
    snn = small_app(300, 3600, seed=9)
    np.testing.assert_array_equal(
        partition_greedy(snn, hw).cluster_of,
        partition_greedy_reference(snn, hw).cluster_of,
    )


# ======================================================================
# dense batched FCFS constructor vs the heapq oracle
# ======================================================================
@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=500))
def test_orders_batch_equals_heapq_oracle(seed):
    rng = np.random.default_rng(seed)
    snn = small_app(
        80 + 30 * (seed % 7), 600 + 300 * (seed % 5), seed=seed,
        recurrent=bool(seed % 2),
    )
    cl = partition_greedy(snn, DYNAP_SE)
    app = sdfg_from_clusters(cl, hw=DYNAP_SE)
    bindings = np.stack([
        rng.integers(0, DYNAP_SE.n_tiles, size=app.n_actors)
        for _ in range(4)
    ])
    batch = build_static_orders_batch(app, bindings, DYNAP_SE)
    for b in range(bindings.shape[0]):
        oracle = SelfTimedExecutor(app, bindings[b], DYNAP_SE).run(
            iterations=1
        ).tile_orders
        assert batch[b] == oracle, b


def test_orders_batch_periods_match_operational_oracle():
    """The period of the batch-constructed schedule must equal the
    operational steady state of replaying those very orders (<= 1e-6)."""
    snn = small_app(200, 2400, seed=3)
    cl = partition_greedy(snn, DYNAP_SE)
    hw = dataclasses.replace(
        DYNAP_SE,
        tile=dataclasses.replace(DYNAP_SE.tile, input_buffer=64,
                                 output_buffer=64),
    )
    app = sdfg_from_clusters(cl, hw=hw)
    rng = np.random.default_rng(1)
    bindings = np.stack([
        bind_ours(cl, hw).binding
        if i == 0 else rng.integers(0, hw.n_tiles, size=app.n_actors)
        for i in range(3)
    ])
    orders = build_static_orders_batch(app, bindings, hw)
    rep = batch_execute(app, bindings, hw, orders, backend="edges")
    for b in range(bindings.shape[0]):
        trace: ExecutionTrace = SelfTimedExecutor(
            app, bindings[b], hw, orders=orders[b]
        ).run(iterations=400)
        assert rep.periods[b] == pytest.approx(
            trace.steady_period(), rel=1e-6
        ), b


def test_orders_batch_single_binding_promotes():
    snn = small_app(120, 1200, seed=4)
    cl = partition_greedy(snn, DYNAP_SE)
    app = sdfg_from_clusters(cl, hw=DYNAP_SE)
    b = bind_ours(cl, DYNAP_SE).binding
    batch = build_static_orders_batch(app, b, DYNAP_SE)
    assert len(batch) == 1
    old, _ = build_static_orders(app, b, DYNAP_SE, iterations=1)
    assert batch[0] == old


def test_single_tile_order_methods_agree():
    """The dense single-tile constructor equals the heapq path at the
    §4.4 step-2 horizon (one firing per actor defines the order)."""
    snn = small_app(180, 2200, seed=8)
    cl = partition_greedy(snn, DYNAP_SE)
    fast, _ = single_tile_order(cl, DYNAP_SE)
    slow, _ = single_tile_order(cl, DYNAP_SE, method="heapq",
                                sim_iterations=1)
    assert fast == slow
    assert sorted(fast) == list(range(cl.n_clusters))


# ======================================================================
# OrderBatch: batched Lemma-1 projection == per-candidate list path
# ======================================================================
@pytest.fixture(scope="module")
def projected():
    snn = small_app(260, 3200, seed=31)
    cl = partition_greedy(snn, DYNAP_SE)
    app = sdfg_from_clusters(cl, hw=DYNAP_SE)
    order, _ = single_tile_order(cl, DYNAP_SE)
    rng = np.random.default_rng(7)
    bindings = np.stack([
        rng.integers(0, DYNAP_SE.n_tiles, size=app.n_actors)
        for _ in range(8)
    ])
    return app, order, bindings


def test_project_order_batch_rows_match_project_order(projected):
    app, order, bindings = projected
    ob = project_order_batch(order, bindings)
    assert isinstance(ob, OrderBatch)
    assert ob.n_graphs == bindings.shape[0] and ob.n_actors == app.n_actors
    for b in range(bindings.shape[0]):
        assert ob.row(b, bindings[b], DYNAP_SE.n_tiles) == project_order(
            list(order), bindings[b], DYNAP_SE.n_tiles
        )


def test_order_batch_periods_match_list_path(projected):
    app, order, bindings = projected
    ob = project_order_batch(order, bindings)
    ol = [project_order(list(order), b, DYNAP_SE.n_tiles) for b in bindings]
    rep_ob = batch_execute(app, bindings, DYNAP_SE, ob, backend="edges")
    rep_ol = batch_execute(app, bindings, DYNAP_SE, ol, backend="edges")
    np.testing.assert_allclose(rep_ob.periods, rep_ol.periods, rtol=1e-9)
    # and both match per-graph Howard on the same order-augmented graphs
    expected = [
        mcr_howard(hardware_aware_sdfg(app, b, DYNAP_SE, o))
        for b, o in zip(bindings, ol)
    ]
    np.testing.assert_allclose(rep_ob.periods, expected, rtol=1e-6)


def test_order_batch_shortcut_stack_preserves_mcr(projected):
    app, order, bindings = projected
    ob = project_order_batch(order, bindings)
    plain = stack_hardware_aware(app, bindings, DYNAP_SE, ob)
    fast = stack_hardware_aware(
        app, bindings, DYNAP_SE, ob, relax_shortcuts=True
    )
    assert fast.n_edges >= plain.n_edges
    np.testing.assert_allclose(
        mcr_batch(plain, backend="edges"),
        mcr_batch(fast, backend="edges"),
        rtol=1e-7,
    )


def test_order_batch_lower_bounds_sound_and_match_legacy(projected):
    app, order, bindings = projected
    ob = project_order_batch(order, bindings)
    ol = [project_order(list(order), b, DYNAP_SE.n_tiles) for b in bindings]
    lo_ob = order_cycle_lower_bounds(app.exec_time, bindings, ob)
    lo_ol = order_cycle_lower_bounds(app.exec_time, bindings, ol)
    np.testing.assert_allclose(lo_ob, lo_ol)
    periods = batch_execute(app, bindings, DYNAP_SE, ob,
                            backend="edges").periods
    assert np.all(lo_ob <= periods + 1e-9)


def test_project_order_batch_appends_missing_actors():
    """Defensive parity with project_order: actors absent from the order
    are appended per tile in id order."""
    binding = np.array([1, 0, 1, 0])
    partial = [2, 0]                    # actors 1 and 3 missing
    ob = project_order_batch(partial, binding[None, :])
    assert ob.row(0, binding, 2) == project_order(partial, binding, 2)


# ======================================================================
# shape-bucket compile cache
# ======================================================================
def test_bucket_sizes_pow2ish():
    from repro.core.engine import _bucket_size

    assert [_bucket_size(x) for x in (1, 2, 3, 4, 5, 6, 7, 9, 13, 17)] == [
        1, 2, 3, 4, 6, 6, 8, 12, 16, 24
    ]
    for x in range(1, 500):
        bx = _bucket_size(x)
        assert bx >= x and bx <= 2 * x


@pytest.mark.parametrize("backend", ("edges", "csr-jit"))
def test_pad_stack_to_buckets_preserves_periods(projected, backend):
    from repro.core import pad_stack_to_buckets

    app, order, bindings = projected
    ob = project_order_batch(order, bindings[:5])
    stack = stack_hardware_aware(app, bindings[:5], DYNAP_SE, ob)
    padded, _ = pad_stack_to_buckets(stack)
    assert padded.n_graphs >= stack.n_graphs
    assert padded.n_edges >= stack.n_edges
    np.testing.assert_allclose(
        mcr_batch(stack, backend=backend),
        mcr_batch(padded, backend=backend)[: stack.n_graphs],
        rtol=1e-9,
    )


def test_cache_counters_hit_on_repeated_shapes(projected):
    app, order, bindings = projected
    ob = project_order_batch(order, bindings)
    reset_compile_cache_stats()
    try:
        batch_execute(app, bindings, DYNAP_SE, ob, backend="edges")
        batch_execute(app, bindings, DYNAP_SE, ob, backend="edges")
        batch_execute(app, bindings[:2], DYNAP_SE,
                      project_order_batch(order, bindings[:2]),
                      backend="edges")
        stats = compile_cache_stats()
        assert stats.hits == 1 and stats.misses == 2
        assert 0.0 < stats.hit_rate < 1.0
        assert stats.as_dict()["n_distinct_shapes"] == 2
    finally:
        reset_compile_cache_stats()


def test_optimizer_generations_share_one_shape(projected):
    """OrderBatch makes the stacked shape generation-invariant: a whole
    optimizer run records exactly ONE distinct scoring shape."""
    snn = small_app(260, 3200, seed=31)
    cl = partition_greedy(snn, DYNAP_SE)
    reset_compile_cache_stats()
    try:
        optimize_binding(cl, DYNAP_SE, population=12, generations=3,
                         rng_seed=0)
        stats = compile_cache_stats()
        # generations at rel_tol 1e-4 + final exact re-score may differ in
        # candidate count (deduped pool) -> at most two distinct shapes
        assert len(stats.shapes) <= 2
        assert stats.hits >= 2
    finally:
        reset_compile_cache_stats()


# ======================================================================
# comm-critical-path guided mutation
# ======================================================================
def _chatty_clusters(n=8) -> ClusteredSNN:
    """A clustered app whose channel 0->4 dominates all traffic."""
    src = np.array([0, 1, 2], dtype=np.int64)
    dst = np.array([4, 2, 3], dtype=np.int64)
    rate = np.array([5000.0, 1.0, 1.0])
    order = np.lexsort((dst, src))
    return ClusteredSNN(
        snn=None,
        cluster_of=np.zeros(n, dtype=np.int32),
        n_clusters=n,
        channel_src=src[order],
        channel_dst=dst[order],
        channel_rate=rate[order],
        inputs_used=np.full(n, 8.0),
        neurons_used=np.full(n, 8.0),
        synapses_used=np.full(n, 30.0),
        out_spikes=np.full(n, 4.0),
        in_spikes=np.full(n, 4.0),
    )


def test_comm_guided_mutate_colocates_heaviest_cut():
    cl = _chatty_clusters()
    hw = dataclasses.replace(DYNAP_SE, n_tiles=16)
    rng = np.random.default_rng(0)
    pop = rng.integers(0, 16, size=(32, cl.n_clusters))
    pop[:, 0] = 0
    pop[:, 4] = 15           # heaviest channel endpoints far apart
    _comm_guided_mutate(
        pop, cl.channel_src, cl.channel_dst, cl.channel_rate, hw, rng
    )
    # every row co-located the dominant channel (moved 0->15 or 4->0)
    assert np.all(pop[:, 0] == pop[:, 4])


def test_comm_guided_mutate_noop_when_no_cut():
    cl = _chatty_clusters()
    hw = dataclasses.replace(DYNAP_SE, n_tiles=16)
    rng = np.random.default_rng(1)
    pop = np.zeros((4, cl.n_clusters), dtype=np.int64)   # all co-located
    before = pop.copy()
    _comm_guided_mutate(
        pop, cl.channel_src, cl.channel_dst, cl.channel_rate, hw, rng
    )
    np.testing.assert_array_equal(pop, before)


def test_optimizer_improves_comm_dominated_app():
    """NoC-bound operating point: link/route costs dominate compute, so
    co-locating chatty clusters is the winning move the comm mutation
    makes reachable.  The optimizer must strictly beat every Eq.-7 seed
    (deterministic under the fixed rng_seed)."""
    comm_hw = dataclasses.replace(
        DYNAP_SE, n_tiles=16,
        t_spike_link=0.4, t_route=5.0, t_spike_encode=0.05,
    )
    snn = small_app(200, 2600, seed=13)
    cl = partition_greedy(snn, comm_hw)
    rep = optimize_binding(
        cl, comm_hw, population=24, generations=5, rng_seed=2
    )
    assert rep.period <= rep.best_seed_period * (1 + 1e-9)
    assert rep.improvement > 0.0
