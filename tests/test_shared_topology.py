"""Stacks whose rows share one topology pack as replicas of one ELL and
relax node-major; stacks whose rows share a base of edges pack that base
the same way and the rest per replica.  Every result and every loop count
is bit-identical to packing the same stack row by row."""

import dataclasses
import math

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (
    DYNAP_SE,
    build_app,
    fuse_stacks,
    order_cycle_lower_bounds,
    partition_greedy,
    project_order_batch,
    sdfg_from_clusters,
    stack_graphs,
    stack_hardware_aware,
)
from repro.core import maxplus as mp
from repro.core.sdfg import SDFG, Channel
from repro.kernels import maxplus_bellman as kbell
from tests.test_maxplus_backends import _ring_stack, random_live_sdfg
from tests.test_solve_counts import GOLDEN, _pad

K = kbell.DEFAULT_K_PROBES
#: the bisection step cap ``_mcr_batch_csr`` gives at its defaults
STEPS = max(4, int(math.ceil(80 / math.log2(K + 1))) + 1)


def _reweighted(stack, seed):
    """``stack``'s topology under row-wise weights, as the candidate
    bindings of one app differ only in their NoC delays."""
    rng = np.random.default_rng(seed)
    w = stack.weights * rng.uniform(0.5, 2.0, stack.weights.shape)
    return mp.EdgeStack(n_actors=stack.n_actors, src=stack.src,
                        dst=stack.dst, tokens=stack.tokens, weights=w)


def _pad_middle(stack, at):
    """An all--inf row inserted before row ``at``."""
    padded = _pad(stack, 1)
    order = np.r_[np.arange(at), stack.n_graphs, np.arange(at, stack.n_graphs)]
    return mp.EdgeStack(n_actors=stack.n_actors, src=padded.src[order],
                        dst=padded.dst[order], tokens=padded.tokens[order],
                        weights=padded.weights[order])


def _live(b):
    g = random_live_sdfg(np.random.default_rng(21), 11)
    return _reweighted(stack_graphs([g] * b), 5)


def _deadlock_rows():
    """One topology with a zero-token cycle (0 -> 1 -> 0); weights drawn
    from both signs, so some rows deadlock and some do not."""
    g = SDFG(n_actors=4, exec_time=np.ones(4),
             channels=[Channel(0, 1, 0, 1.0), Channel(1, 0, 0, 1.0),
                       Channel(1, 2, 0, 1.0), Channel(2, 3, 0, 1.0),
                       Channel(3, 2, 1, 1.0), Channel(3, 3, 1, 1.0)])
    s = stack_graphs([g] * 6)
    w = np.random.default_rng(8).uniform(-2.0, 2.0, s.weights.shape)
    return mp.EdgeStack(n_actors=4, src=s.src, dst=s.dst, tokens=s.tokens,
                        weights=np.where(np.isfinite(s.weights), w, s.weights))


SHARED = {
    "live": (lambda: _live(6), {}),
    "live_pads": (lambda: _pad(_pad_middle(_live(5), 2), 2), {}),
    "ring": (lambda: _pad(_ring_stack(5, 24, 3, shortcuts=False), 1), {}),
    "ring_sc": (lambda: _ring_stack(5, 24, 3, shortcuts=True), {}),
    "deadlock": (lambda: _pad_middle(_deadlock_rows(), 3),
                 {"detect_deadlock": True}),
}


def _solve(packed, n, *, detect_deadlock=False):
    """(lo, hi, has_cycle, deadlocked) and the five counters of one solve
    of a packed stack."""
    operands, lo, hi, has_cycle = packed
    with jax.enable_x64(True):
        out = kbell._dispatch_bisect(
            operands, lo, hi, has_cycle, n_actors=n, rel_tol=1e-8,
            k_probes=K, max_steps=STEPS, max_rounds=0,
            detect_deadlock=detect_deadlock)
        res = [np.asarray(x) for x in out[:4]]
        return res, kbell._tally(out[4], operands, len(lo), K)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int64) if a.dtype == np.float64 else a


@pytest.mark.parametrize("name", sorted(SHARED))
def test_shared_layout_bit_identical_to_per_row(name):
    make, kw = SHARED[name]
    stack = make()
    shared = mp._pack_csr_chunk(stack, None)
    per_row = mp._pack_per_row(stack, None)
    b, n = stack.n_graphs, stack.n_actors
    assert shared[0][1].shape[::2] == (n, b)          # (n, d, B)
    assert per_row[0][1].shape[::2] == (b * n, 1)     # (B*n, d, 1)
    assert shared[0][1].shape[1] == per_row[0][1].shape[1]
    for x, y in zip(shared[1:], per_row[1:]):          # host bounds
        np.testing.assert_array_equal(_bits(x), _bits(y))
    res_s, tally_s = _solve(shared, n, **kw)
    res_p, tally_p = _solve(per_row, n, **kw)
    for x, y in zip(res_s, res_p):
        np.testing.assert_array_equal(_bits(x), _bits(y))
    assert tally_s == tally_p
    assert tally_s["live_probe_rounds"] > 0


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_per_row_layout_keeps_golden_periods(name, monkeypatch):
    """Packed row by row, every golden stack still gives its golden
    periods, whatever layout it takes by default."""
    make, kw, want = GOLDEN[name]
    monkeypatch.setattr(mp, "_pack_csr_chunk", mp._pack_per_row)
    with obs.recording() as rec:
        got = mp._mcr_batch_csr(make(), **kw)
    assert [float(x).hex() for x in got] == want
    assert rec.counters["solve.shared_calls"] == 0


def _two_topologies():
    """Two random graphs of seven actors: they share the self-loop and
    ring columns, and differ in the rest."""
    rng = np.random.default_rng(3)
    return stack_graphs([random_live_sdfg(rng, 7), random_live_sdfg(rng, 7)])


def _disjoint_topologies():
    """Two topologies with no column in common: the second graph's
    channels in reverse order."""
    g = random_live_sdfg(np.random.default_rng(3), 7)
    rev = SDFG(n_actors=g.n_actors, exec_time=g.exec_time,
               channels=list(g.channels)[::-1])
    return stack_graphs([g, rev])


def _wide_per_row():
    """Rows that share only their self-loops and differ in seven more
    in-edges a node: the split would gather as many per-replica slots as
    the per-row pack gathers slots, so it does not pay."""
    n, rng = 16, np.random.default_rng(6)
    rows = []
    for _ in range(2):
        channels = [Channel(i, i, 1, 1.0) for i in range(n)]
        for j in range(n):
            for i in rng.choice(np.delete(np.arange(n), j), 7, replace=False):
                channels.append(Channel(int(i), j, int(i >= j), 1.0))
        rows.append(SDFG(n_actors=n, exec_time=rng.uniform(0.5, 5.0, n),
                         channels=channels))
    return stack_graphs(rows)


HW = dataclasses.replace(DYNAP_SE, n_tiles=16)


def _candidates(app, b, seed=0):
    """``b`` candidate bindings of a Table-1 app on a 2x2 block of tiles,
    stacked as one generation of the binding search stacks them: shared
    self, flow and buffer edges, per-row order edges and shortcuts."""
    g = sdfg_from_clusters(partition_greedy(build_app(app), HW), hw=HW)
    rng = np.random.default_rng(seed)
    pop = rng.choice([0, 1, 4, 5], size=(b, g.n_actors))
    orders = project_order_batch(rng.permutation(g.n_actors), pop)
    stack = stack_hardware_aware(g, pop, HW, orders, relax_shortcuts=True)
    return stack, order_cycle_lower_bounds(g.exec_time, pop, orders)


def _fused_apps():
    """Four candidates each of two apps fused into one solve."""
    return fuse_stacks([_candidates("MLP-MNIST", 4)[0],
                        _candidates("CNN-MNIST", 4, seed=1)[0]])[0]


@pytest.mark.parametrize("b", [1, 6, 8])
@pytest.mark.parametrize("app", ["MLP-MNIST", "ImgSmooth", "CNN-MNIST"])
def test_split_layout_bit_identical_to_per_row(app, b):
    """Binding candidates with all--inf pad rows (one in the middle, two
    at the end) pack split, or shared when one row is real, and solve
    exactly as packed row by row."""
    stack, lo0 = _candidates(app, b)
    stack = _pad(_pad_middle(stack, b // 2), 2)
    lo0 = np.insert(lo0, b // 2, -np.inf)
    lo0 = np.r_[lo0, -np.inf, -np.inf]
    layout = mp._choose_layout(stack)[0]
    assert layout == ("shared" if b == 1 else "split")
    packed = mp._pack_csr_chunk(stack, lo0)
    per_row = mp._pack_per_row(stack, lo0)
    for x, y in zip(packed[1:], per_row[1:]):          # host bounds
        np.testing.assert_array_equal(_bits(x), _bits(y))
    res, tally = _solve(packed, stack.n_actors)
    res_p, tally_p = _solve(per_row, stack.n_actors)
    for x, y in zip(res, res_p):
        np.testing.assert_array_equal(_bits(x), _bits(y))
    relax = tally.pop("relaxations"), tally_p.pop("relaxations")
    assert tally == tally_p
    assert tally["live_probe_rounds"] > 0
    # relaxations count the slots of the layout that ran
    slots = sum(np.shape(w)[1] for w in packed[0][1::3])
    assert relax[0] == (tally["rounds"] * stack.n_graphs * stack.n_actors
                        * slots * K)


def _forced_split(stack, lo0=None):
    """``stack`` packed split whatever the chooser says: the columns
    before the first one its rows differ on (the first half, when they
    differ on none) are the shared base, the rest go per replica."""
    _, ref, same = mp._choose_layout(stack)
    cut = int(np.argmin(same)) if not same.all() else stack.n_edges // 2
    base = same & (np.arange(stack.n_edges) < cut)
    return mp._pack_shared(stack, lo0, ref, base)


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_split_layout_keeps_golden_periods(name, monkeypatch):
    make, kw, want = GOLDEN[name]
    monkeypatch.setattr(mp, "_pack_csr_chunk", _forced_split)
    with obs.recording() as rec:
        got = mp._mcr_batch_csr(make(), **kw)
    assert [float(x).hex() for x in got] == want
    assert rec.counters["solve.split_calls"] == 1


def _one_row_other_tokens():
    s = _live(4)
    tokens = s.tokens.copy()
    tokens[2, 0] += 1
    return mp.EdgeStack(n_actors=s.n_actors, src=s.src, dst=s.dst,
                        tokens=tokens, weights=s.weights)


@pytest.mark.parametrize("make", [_disjoint_topologies, _wide_per_row,
                                  lambda: _live(1)])
def test_other_stacks_pack_per_row(make):
    stack = make()
    assert mp._choose_layout(stack)[0] == "per_row"
    operands = mp._pack_csr_chunk(stack, None)[0]
    assert operands[1].shape[2] == 1
    with obs.recording() as rec:
        mp._mcr_batch_csr(stack)
    assert rec.spans[1].name == "device_solve"
    assert rec.spans[1].attrs["layout"] == "per_row"


@pytest.mark.parametrize("make, layout", [
    (lambda: _live(4), "shared"),
    (lambda: _candidates("CNN-MNIST", 8)[0], "split"),
    (_two_topologies, "split"),
    (_fused_apps, "split"),
    (_one_row_other_tokens, "split"),
    (_disjoint_topologies, "per_row"),
    (_wide_per_row, "per_row"),
])
def test_layout_follows_the_stack(make, layout):
    """Identical rows pack shared; rows with a common base of columns
    pack split where that gathers fewer slices (binding candidates, two
    graphs or two fused apps that share self-loop columns, rows that
    differ in one token); no common column, or a split that gathers no
    fewer slices, per row."""
    stack = make()
    assert mp._choose_layout(stack)[0] == layout
    with obs.recording() as rec:
        got = mp._mcr_batch_csr(stack)
    span = next(s for s in rec.spans if s.name == "device_solve")
    assert span.attrs["layout"] == layout
    with pytest.MonkeyPatch.context() as m:
        m.setattr(mp, "_pack_csr_chunk", mp._pack_per_row)
        want = mp._mcr_batch_csr(stack)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_common_column_after_a_rows_own_goes_per_row():
    """Row 2's first edge (node 0's self-loop) differs in its tokens; the
    columns into node 0 after it are the same in every row, yet they pack
    with the per-row edges, so node 0's slots keep their column order."""
    stack = _one_row_other_tokens()
    layout, _, same = mp._choose_layout(stack)
    assert layout == "split" and not same[0]
    later = (stack.dst[0] == stack.dst[0, 0]) & (np.arange(stack.n_edges) > 0)
    assert later.any() and not same[later].any()
    assert same[stack.dst[0] != stack.dst[0, 0]].all()
    packed = mp._pack_csr_chunk(stack, None)
    res, tally = _solve(packed, stack.n_actors)
    res_p, tally_p = _solve(mp._pack_per_row(stack, None), stack.n_actors)
    for x, y in zip(res, res_p):
        np.testing.assert_array_equal(_bits(x), _bits(y))
    tally.pop("relaxations"), tally_p.pop("relaxations")
    assert tally == tally_p


def test_shared_calls_count_shared_solves_only():
    with obs.recording() as rec:
        mp._mcr_batch_csr(_live(4))
        mp._mcr_batch_csr(_disjoint_topologies())
        mp._mcr_batch_csr(_ring_stack(3, 16, 1, shortcuts=True))
        mp._mcr_batch_csr(_two_topologies())
    assert rec.counters["solve.calls"] == 4
    assert rec.counters["solve.shared_calls"] == 2
    assert rec.counters["solve.split_calls"] == 1
    layouts = [s.attrs["layout"] for s in rec.spans
               if s.name == "device_solve"]
    assert layouts == ["shared", "per_row", "shared", "split"]


@pytest.mark.parametrize("name, layout", [("live_pads", "per_row"),
                                          ("ring_sc", "per_row")])
def test_fused_member_keeps_its_periods(name, layout):
    """A shared stack fused with a per-row one solves, on its rows,
    bit-identically to the stack alone, in one call of the fused pack's
    layout."""
    make, kw = SHARED[name]
    stack = make()
    fused, (rows, _) = fuse_stacks([stack, _disjoint_topologies()])
    assert mp._choose_layout(fused)[0] == layout
    alone = mp._mcr_batch_csr(stack, **kw)
    with obs.recording() as rec:
        got = mp._mcr_batch_csr(fused, **kw)
    np.testing.assert_array_equal(_bits(got[rows]), _bits(alone))
    assert rec.counters["solve.calls"] == 1
    span = next(s for s in rec.spans if s.name == "device_solve")
    assert span.attrs["layout"] == layout
