"""Cross-region fused scoring and the serving layer (ISSUE 9 tentpole):
fused EdgeStack analysis == sequential analysis, lockstep fused binding
search == standalone search, and coalesced rebalancing via
:class:`ServingQueue` / ``defer_rebalances``."""

import dataclasses

import numpy as np
import pytest

from repro.core import (
    DYNAP_SE,
    AdmissionController,
    ServingQueue,
    batch_execute,
    batch_execute_fused,
    fuse_stacks,
    mcr_batch,
    optimize_binding_graph,
    optimize_binding_graphs_fused,
    partition_greedy,
    prepare_execution,
    project_order_batch,
    sdfg_from_clusters,
    single_tile_order,
    small_app,
)

HW64 = dataclasses.replace(DYNAP_SE, n_tiles=64)


def _compiled(seed, neurons=170, synapses=2100):
    snn = small_app(neurons, synapses, seed=seed)
    cl = partition_greedy(snn, DYNAP_SE)
    app = sdfg_from_clusters(cl, hw=DYNAP_SE)
    order, _ = single_tile_order(cl, DYNAP_SE)
    return app, order


def _bindings(app, n_rows, seed):
    rng = np.random.default_rng(seed)
    return np.stack([
        rng.integers(0, DYNAP_SE.n_tiles, size=app.n_actors)
        for _ in range(n_rows)
    ])


# ======================================================================
# engine layer: fused stacks solve row-identically
# ======================================================================
# "edges" is what "auto" resolves to on a host; "csr-jit" is the device
# solve, whose fused (and bucket-padded) pack differs from each member's
# own pack -- row-locality says no row's period may notice
BACKENDS = pytest.mark.parametrize("backend", ("edges", "csr-jit"))


@BACKENDS
def test_fuse_stacks_rows_solve_identically(backend):
    preps = []
    for seed, rows in ((1, 3), (2, 5), (3, 2)):
        app, order = _compiled(seed)
        b = _bindings(app, rows, seed)
        ob = project_order_batch(order, b)
        preps.append(prepare_execution(app, b, DYNAP_SE, ob))
    fused, slices = fuse_stacks([p.stack for p in preps])
    assert fused.n_graphs == sum(p.n_rows for p in preps)
    got = mcr_batch(fused, backend=backend)
    for p, s in zip(preps, slices):
        alone = mcr_batch(p.stack, backend=backend)
        np.testing.assert_array_equal(got[s], alone)


@BACKENDS
def test_batch_execute_fused_matches_sequential(backend):
    preps, reports = [], []
    for seed, rows in ((4, 4), (5, 3)):
        app, order = _compiled(seed)
        b = _bindings(app, rows, seed)
        ob = project_order_batch(order, b)
        preps.append(
            prepare_execution(app, b, DYNAP_SE, ob, with_energy=True)
        )
        reports.append(
            batch_execute(app, b, DYNAP_SE, ob, backend=backend,
                          with_energy=True)
        )
    fused_reports = batch_execute_fused(preps, backend=backend)
    for fr, sr in zip(fused_reports, reports):
        np.testing.assert_allclose(fr.periods, sr.periods, rtol=1e-12)
        np.testing.assert_allclose(fr.energies, sr.energies, rtol=1e-12)


# ======================================================================
# optimizer layer: lockstep fused search == standalone search
# ======================================================================
def _task(seed, *, generations, population=10):
    app, order = _compiled(seed)
    seed_b = (np.arange(app.n_actors) + seed) % DYNAP_SE.n_tiles
    return dict(
        app=app, hw=DYNAP_SE, single_order=order,
        seed_bindings={"seed": seed_b},
        population=population, generations=generations, elite=4,
        rng_seed=seed,
    )


@BACKENDS
def test_fused_binding_search_bit_matches_sequential(backend):
    """Equal generation counts: every tick fuses into exactly one solve,
    and each search's result is bit-for-bit its standalone run."""
    tasks = [_task(7, generations=2), _task(8, generations=2)]
    seq = [
        optimize_binding_graph(
            t["app"], t["hw"], t["single_order"], backend=backend,
            **{k: v for k, v in t.items()
               if k not in ("app", "hw", "single_order")},
        )
        for t in tasks
    ]
    fused = optimize_binding_graphs_fused(tasks, backend=backend)
    for f, s in zip(fused, seq):
        np.testing.assert_array_equal(f.binding, s.binding)
        assert f.period == s.period
        assert f.n_stack_builds == s.n_stack_builds
        assert [g.best_period for g in f.history] == \
               [g.best_period for g in s.history]


@BACKENDS
def test_fused_binding_search_mixed_generations(backend):
    """Unequal horizons exercise the per-(tick, tolerance) grouping: a
    finished search's tight final re-score must never be fused with
    another search's loose generation scoring."""
    tasks = [_task(9, generations=1), _task(10, generations=3)]
    seq = [
        optimize_binding_graph(
            t["app"], t["hw"], t["single_order"], backend=backend,
            **{k: v for k, v in t.items()
               if k not in ("app", "hw", "single_order")},
        )
        for t in tasks
    ]
    fused = optimize_binding_graphs_fused(tasks, backend=backend)
    for f, s in zip(fused, seq):
        np.testing.assert_array_equal(f.binding, s.binding)
        assert f.period == s.period
        assert f.n_stack_builds == s.n_stack_builds


# ======================================================================
# runtime/serving layer: deferral + coalesced flush
# ======================================================================
def _registered_controller(n_apps=6, seed0=300, **kw):
    ctl = AdmissionController(
        HW64, placement="joint", joint_budget=(1, 4), **kw
    )
    names = []
    for i in range(n_apps):
        snn = small_app(150, 1800, seed=seed0 + i)
        snn.name = f"sv{i}"
        ctl.register(snn)
        names.append(snn.name)
    return ctl, names


def _rebalance_count(ctl):
    return sum(1 for e in ctl.events if e.kind == "rebalance")


def test_defer_rebalances_records_then_flushes_once():
    ctl, names = _registered_controller()
    for n in names[:2]:
        ctl.admit(n, n_tiles_request=3)
    before = _rebalance_count(ctl)
    with ctl.defer_rebalances():
        for n in names[2:5]:
            ctl.admit(n, n_tiles_request=3)
        assert _rebalance_count(ctl) == before   # recorded, not run
    after = _rebalance_count(ctl)
    assert after == before + 1                   # ONE merged flush
    assert set(ctl.state.allocated) == set(names[:5])


def test_flush_rebalances_noop_when_nothing_pending():
    ctl, names = _registered_controller(n_apps=2)
    ctl.admit(names[0], n_tiles_request=3)
    assert ctl.flush_rebalances() == 0


def test_serving_queue_window_validation():
    ctl, _ = _registered_controller(n_apps=2)
    with pytest.raises(ValueError):
        ServingQueue(ctl, coalesce_window=0)


def test_serving_queue_drain_matches_per_event_residency():
    """The coalesced drain must land on the same resident set as the
    per-event loop, with fewer rebalances and a clean never-regress
    trace."""
    stream = ["sv0", "sv1", "sv2", "sv0", "sv3", "sv4", "sv1", "sv5"]

    ctl_a, _ = _registered_controller()
    for n in stream:
        if n in ctl_a.state.allocated:
            ctl_a.evict(n)
        else:
            ctl_a.admit(n, n_tiles_request=3)

    ctl_b, _ = _registered_controller()
    q = ServingQueue(ctl_b, coalesce_window=4)
    resident = set()
    for n in stream:
        if n in resident:
            q.submit_evict(n)
            resident.discard(n)
        else:
            q.submit_admit(n, n_tiles_request=3)
            resident.add(n)
    stats = q.drain()

    assert q.pending == 0
    assert stats["processed"] == len(stream)
    assert stats["rejected"] == 0 and stats["skipped"] == 0
    assert set(ctl_b.state.allocated) == set(ctl_a.state.allocated)
    assert stats["flushes"] == 2                     # ceil(8 / 4)
    assert stats["coalesced_events"] > 0
    assert _rebalance_count(ctl_b) <= _rebalance_count(ctl_a)
    # admit latency percentiles are well-formed
    assert stats["admit_latency_p99_s"] >= stats["admit_latency_p50_s"] >= 0

    prev = None
    for e in ctl_b.events:
        if e.kind == "rebalance" and prev is not None and prev > 0:
            assert e.chip_throughput >= prev * (1 - 1e-6)
        if e.chip_throughput and e.chip_throughput > 0:
            prev = e.chip_throughput


def test_serving_queue_skips_evicting_non_resident():
    ctl, names = _registered_controller(n_apps=2)
    q = ServingQueue(ctl, coalesce_window=2)
    q.submit_evict(names[1])                 # never admitted
    q.submit_admit(names[0], n_tiles_request=3)
    stats = q.drain()
    assert stats["skipped"] == 1 and stats["admitted"] == 1
    kinds = {t.app: t.status for t in q.tickets}
    assert kinds[names[1]] == "skipped" and kinds[names[0]] == "ok"


# ======================================================================
# speculative pre-compilation (PrecompilePool)
# ======================================================================
def test_precompile_pool_predicts_by_decayed_frequency():
    from repro.core import PrecompilePool

    ctl, names = _registered_controller(n_apps=3)
    pool = PrecompilePool(ctl, decay=0.9, top_k=2)
    for _ in range(3):
        pool.observe(names[0])
    pool.observe(names[1])
    assert pool.predict() == [names[0], names[1]]
    # recency beats stale volume under decay
    for _ in range(4):
        pool.observe(names[2])
    assert pool.predict(1) == [names[2]]


def test_precompile_pool_warm_and_hit_accounting():
    from repro.core import PrecompilePool

    ctl = AdmissionController(HW64, placement="joint", joint_budget=(1, 4))
    apps = {}
    for i in range(3):
        snn = small_app(150, 1800, seed=400 + i)
        snn.name = f"pc{i}"
        apps[snn.name] = snn
    pool = PrecompilePool(ctl, source=apps, top_k=2)

    pool.observe("pc0")
    pool.observe("pc1")
    warmed = pool.warm()
    assert sorted(warmed) == ["pc0", "pc1"]
    assert pool.warmed_artifacts == 2
    assert ("pc0", ctl.hw) in ctl.artifacts

    assert pool.ensure("pc0") is True          # speculation paid design
    assert pool.ensure("pc2") is False         # cold: registered inline
    assert ("pc2", ctl.hw) in ctl.artifacts
    assert pool.hits == 1 and pool.misses == 1
    assert pool.stats()["hit_rate"] == 0.5

    # unresolvable prediction is skipped, never fabricated
    pool2 = PrecompilePool(ctl, top_k=1)
    pool2.observe("ghost")
    assert pool2.warm() == []


def test_serving_queue_precompile_integration():
    from repro.core import PrecompilePool

    ctl = AdmissionController(HW64, placement="joint", joint_budget=(1, 4))
    apps = {}
    for i in range(2):
        snn = small_app(150, 1800, seed=500 + i)
        snn.name = f"pi{i}"
        apps[snn.name] = snn
    pool = PrecompilePool(ctl, source=apps, top_k=2)
    q = ServingQueue(ctl, coalesce_window=2, precompile=pool)
    q.submit_admit("pi0", n_tiles_request=3)
    q.submit_admit("pi1", n_tiles_request=3)
    stats = q.drain()
    # warm() ran before the first apply: both admissions hit
    assert stats["precompile"]["hits"] == 2
    assert stats["precompile"]["misses"] == 0
    assert stats["admitted"] == 2


# ======================================================================
# async front end: cancellation + per-tenant quotas
# ======================================================================
def test_ticket_cancellation_lifecycle():
    ctl, names = _registered_controller(n_apps=3)
    q = ServingQueue(ctl, coalesce_window=2)
    t0 = q.submit_admit(names[0], n_tiles_request=3)
    t1 = q.submit_admit(names[1], n_tiles_request=3)
    assert q.cancel(t0) is True
    assert t0.status == "cancelled"
    assert q.cancel(t0) is False                 # idempotent
    stats = q.drain()
    assert stats["cancelled"] == 1 and stats["admitted"] == 1
    assert t1.status == "ok"
    assert names[0] not in ctl.state.allocated   # never applied
    assert q.cancel(t1) is False                 # drained: too late
    rejects = [e for e in ctl.events if e.kind == "reject"]
    assert [e.reason for e in rejects] == ["cancelled"]
    assert rejects[0].app == names[0]


def test_tenant_quota_rejects_without_placement():
    ctl, names = _registered_controller(n_apps=2)
    q = ServingQueue(ctl, coalesce_window=2, quotas={names[0]: 2})
    q.submit_admit(names[0], n_tiles_request=3)   # over quota
    q.submit_admit(names[1], n_tiles_request=3)
    stats = q.drain()
    assert stats["quota_rejections"] == 1
    assert stats["rejected"] == 1 and stats["admitted"] == 1
    assert names[0] not in ctl.state.allocated
    rejects = [e for e in ctl.events if e.kind == "reject"]
    assert [e.reason for e in rejects] == ["quota"]
    # under-quota re-submission passes
    q.set_quota(names[0], 8)
    q.submit_admit(names[0], n_tiles_request=3)
    assert q.drain()["admitted"] == 1


def test_quota_uses_cluster_count_when_no_explicit_request():
    ctl, names = _registered_controller(n_apps=1)
    art = ctl.artifacts[(names[0], ctl.hw)]
    q = ServingQueue(
        ctl, coalesce_window=1,
        quotas={names[0]: art.clustered.n_clusters - 1},
    )
    q.submit_admit(names[0])                      # implicit full footprint
    stats = q.drain()
    assert stats["quota_rejections"] == 1


def test_drain_reports_wait_service_breakdown():
    ctl, names = _registered_controller(n_apps=3)
    q = ServingQueue(ctl, coalesce_window=2)
    for n in names[:3]:
        q.submit_admit(n, n_tiles_request=3)
    stats = q.drain()
    for key in ("queue_wait_p50_s", "queue_wait_p99_s",
                "service_p50_s", "service_p99_s"):
        assert stats[key] >= 0.0
    assert stats["queue_wait_p99_s"] >= stats["queue_wait_p50_s"]
    assert stats["service_p99_s"] >= stats["service_p50_s"]
    # per-ticket: end-to-end latency decomposes exactly
    for t in q.tickets:
        if t.status == "ok":
            assert t.latency_s == pytest.approx(t.wait_s + t.service_s)
