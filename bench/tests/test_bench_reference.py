"""The plain reference agrees with the program where the program is exact."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import check, deploy, reference
from repro.core import EdgeStack, mcr_batch

ROOT = Path(__file__).resolve().parents[2]


def live_stack(seed, b=24, n=10, e=36):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=(b, e))
    dst = rng.integers(0, n, size=(b, e))
    tok = rng.integers(1, 4, size=(b, e))
    w = rng.uniform(0.1, 9.0, size=(b, e))
    w[:, -4:] = -np.inf                 # absent slots
    w[-1] = -np.inf                     # a padding row: no cycle
    return EdgeStack(n_actors=n, src=src, dst=dst, tokens=tok, weights=w)


@pytest.mark.parametrize("seed", [0, 1])
def test_max_cycle_ratio_matches_exact_search(seed):
    st = live_stack(seed)
    want = mcr_batch(st, backend="edges", rel_tol=1e-13)
    got = reference.stack_ratios(
        {"n_actors": st.n_actors, "src": st.src, "dst": st.dst,
         "tokens": st.tokens, "weights": st.weights}, range(st.n_graphs))
    assert np.isneginf(got[-1]) and np.isneginf(want[-1])
    assert np.allclose(got[:-1], want[:-1], rtol=1e-12, atol=0)


def test_float32_control_is_far_from_float64():
    st = live_stack(2)
    d = {"n_actors": st.n_actors, "src": st.src, "dst": st.dst,
         "tokens": st.tokens, "weights": st.weights}
    rows = range(st.n_graphs - 1)
    hi = reference.stack_ratios(d, rows)
    lo = reference.stack_ratios(d, rows, dtype=np.float32)
    gap = np.max(np.abs(lo - hi) / hi)
    assert 1e-9 < gap < 1e-5


SMALL_APPS = ["ImgSmooth", "MLP-MNIST", "EdgeDet"]


def small_config(placement):
    """The cell's chip and builder with the three smallest Table-1 apps."""
    cfg = json.loads(
        (ROOT / "bench" / "configs" / "table1_dynap16.json").read_text())
    cfg["tenants"]["apps"] = list(SMALL_APPS)
    cfg["controller"] = {"placement": placement}
    if placement == "joint":
        cfg["controller"].update(joint_budget=[1, 4], full_rebalance_every=0)
    return cfg


@pytest.mark.parametrize("placement", ["joint", "isolated"])
def test_app_periods_match_controller(placement):
    dep = deploy.build(small_config(placement))
    for name in dep.tenants:
        dep.ctl.admit(name, n_tiles_request=dep.requests[name])
    dep.ctl.evict(list(dep.tenants)[1])
    # joint: cached per-component records; isolated: per-admission reports
    assert check.app_period_gap(dep) < 1e-8
    assert check.app_period_gap(dep, control=True) > 1e-8
    assert check.ownership_errors(dep, set(dep.ctl.state.allocated)) == 0
    assert check.ownership_errors(dep, set(list(dep.tenants)[:2])) > 0
