"""Max-Plus analysis: three evaluators must agree; brute force on tiny graphs."""

import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.core.maxplus import (
    maxplus_matrix,
    mcm_power_iteration,
    mcr_binary_search,
    mcr_howard,
)
from repro.core.sdfg import SDFG, Channel


def brute_force_mcr(g: SDFG) -> float:
    """Enumerate all simple cycles (tiny graphs only)."""
    src, dst, w, m = g.edges_arrays()
    n = g.n_actors
    best = -np.inf
    edges = list(zip(src.tolist(), dst.tolist(), w.tolist(), m.tolist()))
    # enumerate cycles by DFS from each start node
    def dfs(path_nodes, path_edges, node):
        nonlocal best
        for e, (s, d, ww, mm) in enumerate(edges):
            if s != node:
                continue
            if d == path_nodes[0]:
                wsum = sum(ww2 for (_, _, ww2, _) in path_edges) + ww
                msum = sum(mm2 for (_, _, _, mm2) in path_edges) + mm
                if msum > 0:
                    best = max(best, wsum / msum)
            elif d not in path_nodes:
                dfs(path_nodes + [d], path_edges + [(s, d, ww, mm)], d)

    for start in range(n):
        dfs([start], [], start)
    return best


def random_live_sdfg(rng: np.random.Generator, n: int) -> SDFG:
    """Random strongly-cyclic live event graph."""
    tau = rng.uniform(0.5, 5.0, size=n)
    channels = [Channel(i, i, 1, 1.0, kind="self") for i in range(n)]
    # a ring with one token guarantees a cycle through all actors
    for i in range(n):
        channels.append(Channel(i, (i + 1) % n, 1 if i == n - 1 else 0, 1.0))
    # extra random edges; backward edges carry a token to preserve liveness
    n_extra = int(rng.integers(0, 2 * n))
    for _ in range(n_extra):
        i, j = int(rng.integers(n)), int(rng.integers(n))
        if i == j:
            continue
        channels.append(Channel(i, j, 1 if j <= i else int(rng.integers(0, 3)), 1.0,
                                delay=float(rng.uniform(0, 2.0))))
    g = SDFG(n_actors=n, exec_time=tau, channels=channels)
    g.validate()
    return g


@pytest.mark.parametrize("seed", range(12))
def test_howard_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    g = random_live_sdfg(rng, int(rng.integers(2, 6)))
    assert g.is_live()
    exact = brute_force_mcr(g)
    howard = mcr_howard(g)
    assert np.isclose(howard, exact, rtol=1e-9), (howard, exact)


@pytest.mark.parametrize("seed", range(8))
def test_howard_matches_binary_search(seed):
    rng = np.random.default_rng(100 + seed)
    g = random_live_sdfg(rng, int(rng.integers(3, 20)))
    howard = mcr_howard(g)
    binary = mcr_binary_search(g, tol=1e-7)
    assert np.isclose(howard, binary, atol=1e-5), (howard, binary)


@pytest.mark.parametrize("seed", range(6))
def test_power_iteration_matches_howard_single_token(seed):
    """T-matrix power iteration is exact when all markings are <= 1."""
    rng = np.random.default_rng(200 + seed)
    n = int(rng.integers(3, 12))
    tau = rng.uniform(0.5, 5.0, size=n)
    channels = [Channel(i, i, 1, 1.0, kind="self") for i in range(n)]
    for i in range(n):
        channels.append(Channel(i, (i + 1) % n, 1 if i == n - 1 else 0, 1.0))
    for _ in range(n):
        i, j = int(rng.integers(n)), int(rng.integers(n))
        if i != j:
            channels.append(Channel(i, j, 1, 1.0))
    g = SDFG(n_actors=n, exec_time=tau, channels=channels)
    howard = mcr_howard(g)
    power = mcm_power_iteration(maxplus_matrix(g), iters=400, use_kernel=False)
    assert np.isclose(power, howard, rtol=1e-3), (power, howard)


@pytest.mark.parametrize("seed", range(4))
def test_power_iteration_converges_on_strongly_connected(seed):
    """Convergence check after the renormalization cleanup: power iteration
    (kernel matvec path included) agrees with Howard on random strongly-
    connected event graphs whose markings are all <= 1 (where T is exact)."""
    rng = np.random.default_rng(300 + seed)
    n = int(rng.integers(4, 16))
    tau = rng.uniform(0.5, 5.0, size=n)
    channels = [Channel(i, i, 1, 1.0, kind="self") for i in range(n)]
    # a random Hamiltonian cycle makes the graph strongly connected
    perm = rng.permutation(n)
    for a, b in zip(perm, np.roll(perm, -1)):
        channels.append(Channel(int(a), int(b), 1, 1.0,
                                delay=float(rng.uniform(0, 1.0))))
    for _ in range(2 * n):
        i, j = int(rng.integers(n)), int(rng.integers(n))
        if i != j:
            channels.append(Channel(i, j, 1, 1.0))
    g = SDFG(n_actors=n, exec_time=tau, channels=channels)
    assert g.is_live()
    howard = mcr_howard(g)
    for use_kernel in (False, True):
        power = mcm_power_iteration(
            maxplus_matrix(g), iters=400, use_kernel=use_kernel
        )
        assert np.isclose(power, howard, rtol=1e-3), (use_kernel, power, howard)


@pytest.mark.parametrize("seed", range(12))
def test_howard_matches_brute_force_off_cycle_nodes(seed):
    """Self-loops first, as every actor of a hardware-aware SDFG has, then
    random edges without a ring: not strongly connected, so improved
    policies hang tails into cycles that other starts found."""
    rng = np.random.default_rng(500 + seed)
    n = int(rng.integers(3, 7))
    channels = [Channel(i, i, int(rng.integers(1, 3)), 1.0, kind="self")
                for i in range(n)]
    for _ in range(int(rng.integers(n, 3 * n))):
        i, j = int(rng.integers(n)), int(rng.integers(n))
        # a backward edge carries a token, so every cycle is live
        tokens = int(rng.integers(1, 3)) if j <= i else int(rng.integers(0, 2))
        channels.append(Channel(i, j, tokens, 1.0,
                                delay=float(rng.uniform(0, 2.0))))
    g = SDFG(n_actors=n, exec_time=rng.uniform(0.5, 5.0, size=n),
             channels=channels)
    exact = brute_force_mcr(g)
    howard = mcr_howard(g)
    assert np.isclose(howard, exact, rtol=1e-12), (howard, exact)


def test_acyclic_graph_reports_minus_inf():
    g = SDFG(n_actors=3, exec_time=np.array([1.0, 2.0, 3.0]),
             channels=[Channel(0, 1, 0, 1.0), Channel(1, 2, 1, 1.0)])
    assert mcr_howard(g) == -np.inf


def test_deadlocked_graph_reports_inf():
    # 0 -> 1 -> 0 with no tokens anywhere on the cycle
    g = SDFG(
        n_actors=2,
        exec_time=np.array([1.0, 1.0]),
        channels=[Channel(0, 1, 0, 1.0), Channel(1, 0, 0, 1.0)],
    )
    assert not g.is_live()
    assert mcr_howard(g) == np.inf


def test_two_cycle_example():
    """Hand-checked: cycle A (tau 2+3, 1 token) vs B (tau 2+4+1, 2 tokens)."""
    g = SDFG(
        n_actors=3,
        exec_time=np.array([2.0, 3.0, 4.0]),
        channels=[
            Channel(0, 1, 0, 1.0),
            Channel(1, 0, 1, 1.0),          # cycle 0-1: (3+2)/1 = 5
            Channel(1, 2, 0, 1.0),
            Channel(2, 0, 2, 1.0, delay=1.0),  # cycle 0-1-2: (3+4+2+1)/2 = 5
        ],
    )
    assert np.isclose(mcr_howard(g), 5.0)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_property_mcr_scale_invariance(seed):
    """MCR scales linearly with execution times (max-plus homogeneity)."""
    rng = np.random.default_rng(seed)
    g = random_live_sdfg(rng, int(rng.integers(2, 10)))
    base = mcr_howard(g)
    g2 = SDFG(g.n_actors, g.exec_time * 3.0,
              [Channel(c.src, c.dst, c.tokens, c.rate, c.delay * 3.0, c.kind)
               for c in g.channels], g.name)
    assert np.isclose(mcr_howard(g2), 3.0 * base, rtol=1e-9)


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_property_adding_tokens_never_slows(seed):
    """More initial tokens (bigger buffers) can only improve throughput."""
    rng = np.random.default_rng(seed)
    g = random_live_sdfg(rng, int(rng.integers(2, 8)))
    base = mcr_howard(g)
    bumped = SDFG(
        g.n_actors,
        g.exec_time,
        [Channel(c.src, c.dst, c.tokens + 1, c.rate, c.delay, c.kind)
         for c in g.channels],
        g.name,
    )
    assert mcr_howard(bumped) <= base + 1e-9
