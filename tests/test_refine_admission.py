"""Admission with binding refinement on a full chip, against the plain
reference: each admission evicts the oldest resident and refines the
binding on exactly the freed tiles with the throughput-in-the-loop search,
its per-row stacks solved by the jitted device program."""

import dataclasses

import numpy as np
import pytest

from repro import obs
from repro.core import (
    DYNAP_SE,
    AdmissionController,
    bind_ours,
    bind_pycarl,
    bind_spinemap,
    mcr_batch,
    small_app,
)
from repro.core.maxplus import mcr_howard
from repro.core.runtime import project_order
from repro.core.schedule import hardware_aware_sdfg

HW = dataclasses.replace(DYNAP_SE, n_tiles=8)
BUDGET = (2, 8)
APPS = {"app0": (180, 2200, 50), "app1": (150, 1800, 51),
        "app2": (210, 2600, 52)}


def scenario():
    """Fill the 8-tile chip with two 4-tile residents, then evict the
    oldest and admit a third app onto its 4 freed tiles, recording that
    admission's spans, counters and device solves."""
    from repro.core import maxplus

    ctl = AdmissionController(HW, placement="isolated",
                              optimize_budget=BUDGET)
    for name, (n, syn, seed) in APPS.items():
        snn = small_app(n, syn, seed=seed)
        snn.name = name
        ctl.register(snn)
    ctl.admit("app0", n_tiles_request=4)
    ctl.admit("app1", n_tiles_request=4)
    freed = sorted(ctl.evict("app0"))
    solves = []
    solve = maxplus._mcr_batch_csr

    def recorded(stack, **kw):
        periods = solve(stack, **kw)
        solves.append({"stack": stack, "rel_tol": kw["rel_tol"],
                       "periods": np.array(periods)})
        return periods

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(maxplus, "_mcr_batch_csr", recorded)
        with obs.recording() as rec:
            report = ctl.admit("app2", n_tiles_request=4)
    return {"ctl": ctl, "freed": freed, "report": report, "rec": rec,
            "solves": solves}


@pytest.fixture(scope="module")
def runs():
    """Two runs of the scenario, with ``"auto"`` resolved to the jitted
    device solve as on the chip."""
    import repro.core.engine as engine

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine, "_engine_on_accelerator", lambda: True)
        return scenario(), scenario()


def howard_period(ctl, name, binding):
    art = ctl.artifacts[(name, ctl.hw)]
    orders = project_order(art.single_order, np.asarray(binding), HW.n_tiles)
    return mcr_howard(hardware_aware_sdfg(art.graph, np.asarray(binding),
                                          HW, orders))


def test_reported_period_is_the_exact_period_of_the_refined_binding(runs):
    run = runs[0]
    rep, ctl = run["report"], run["ctl"]
    art = ctl.artifacts[("app2", ctl.hw)]
    g = hardware_aware_sdfg(art.graph, rep.binding, HW, rep.orders)
    period = 1.0 / rep.throughput
    assert period == pytest.approx(mcr_howard(g), rel=1e-10)
    assert rep.orders == project_order(art.single_order, rep.binding,
                                       HW.n_tiles)


def test_never_worse_than_any_seed(runs):
    run = runs[0]
    ctl, freed = run["ctl"], run["freed"]
    art = ctl.artifacts[("app2", ctl.hw)]
    sub = dataclasses.replace(HW, n_tiles=len(freed))
    tiles = np.asarray(freed)
    seeds = [tiles[b(art.clustered, sub).binding]
             for b in (bind_ours, bind_pycarl, bind_spinemap)]
    period = 1.0 / run["report"].throughput
    # the final pool is ranked at rel_tol 1e-8: a seed can only win back
    # what lies inside that tolerance
    for seed in seeds:
        assert period <= howard_period(ctl, "app2", seed) * (1 + 2e-8)
    (sp,) = [s for s in run["rec"].spans if s.name == "refine"]
    assert sp.attrs["period"] <= sp.attrs["seed_period"]


def test_every_solve_agrees_with_the_edges_reference(runs):
    solves = runs[0]["solves"]
    assert sorted(s["rel_tol"] for s in solves) == [1e-8, 1e-4, 1e-4]
    for s in solves:
        ref = mcr_batch(s["stack"], backend="edges", rel_tol=1e-12)
        fin = np.isfinite(ref)
        assert np.array_equal(fin, np.isfinite(s["periods"]))
        gap = np.abs(s["periods"][fin] - ref[fin])
        assert np.all(gap <= s["rel_tol"] * np.maximum(1.0, np.abs(ref[fin])))


def test_every_cluster_sits_on_a_freed_tile(runs):
    run = runs[0]
    assert set(run["report"].binding.tolist()) <= set(run["freed"])
    assert sorted(run["ctl"].state.allocated["app2"]) == run["freed"]


def test_two_runs_give_the_same_binding(runs):
    a, b = runs
    assert np.array_equal(a["report"].binding, b["report"].binding)
    assert a["report"].throughput == b["report"].throughput


def test_refine_counters_count_the_real_rows_of_per_row_solves(runs):
    run = runs[0]
    counters, solves = run["rec"].counters, run["solves"]
    real = sum(int(np.isfinite(s["stack"].weights).any(axis=1).sum())
               for s in solves)
    (sp,) = [s for s in run["rec"].spans if s.name == "refine"]
    assert sp.attrs["rows"] == real
    assert counters["solve.calls"] == len(solves) == BUDGET[0] + 1
    assert counters["solve.shared_calls"] == 0
    dev = [s for s in run["rec"].spans if s.name == "device_solve"]
    assert {s.attrs["layout"] for s in dev} == {"per_row"}
    assert all(s.request == sp.request and s.start_ns >= sp.start_ns
               and s.end_ns <= sp.end_ns for s in dev)


def test_refine_span_carries_its_attrs(runs):
    run = runs[0]
    (sp,) = [s for s in run["rec"].spans if s.name == "refine"]
    (admit,) = [s for s in run["rec"].spans if s.name == "admit"]
    assert sp.request == admit.id
    assert sp.attrs["generations"] == BUDGET[0]
    assert sp.attrs["population"] == BUDGET[1]
    assert set(sp.attrs) == {"generations", "population", "rows",
                             "seed_period", "period"}
    assert sp.attrs["period"] == pytest.approx(
        1.0 / run["report"].throughput, rel=1e-8)
    # the search ran inside the span, and the exact report after it
    names = [s.name for s in run["rec"].spans if s.request == admit.id]
    assert names.index("refine") < names.index("report_score")
    assert "subset_scoring" not in names


def test_the_search_is_the_same_on_every_row_of_the_mesh():
    """On a 4x4 mesh the four rows are the same NoC up to a shift, so a
    refinement on any row scores the same candidates to the same periods."""
    from repro.core import optimize_binding, partition_greedy, single_tile_order

    hw = dataclasses.replace(DYNAP_SE, n_tiles=16)
    c = partition_greedy(small_app(180, 2200, seed=50), hw)
    order, _ = single_tile_order(c, hw)
    reps = [optimize_binding(c, hw, single_order=order, generations=1,
                             population=8, allowed_tiles=range(4 * r, 4 * r + 4),
                             backend="edges")
            for r in range(4)]
    for r, rep in enumerate(reps):
        assert np.array_equal(rep.binding - 4 * r, reps[0].binding)
        assert rep.period == reps[0].period
        assert rep.seed_periods == reps[0].seed_periods
