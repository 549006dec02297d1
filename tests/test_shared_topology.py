"""Stacks whose rows share one topology pack as replicas of one ELL and
relax node-major: every result and every loop count is bit-identical to
packing the same stack row by row."""

import math

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import maxplus as mp
from repro.core import stack_graphs
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
    rng = np.random.default_rng(3)
    return stack_graphs([random_live_sdfg(rng, 7), random_live_sdfg(rng, 7)])


def _one_row_other_tokens():
    s = _live(4)
    tokens = s.tokens.copy()
    tokens[2, 0] += 1
    return mp.EdgeStack(n_actors=s.n_actors, src=s.src, dst=s.dst,
                        tokens=tokens, weights=s.weights)


@pytest.mark.parametrize("make", [_two_topologies, _one_row_other_tokens,
                                  lambda: _live(1)])
def test_other_stacks_pack_per_row(make):
    stack = make()
    assert mp._shared_topology(stack) is None
    operands = mp._pack_csr_chunk(stack, None)[0]
    assert operands[1].shape[2] == 1
    with obs.recording() as rec:
        mp._mcr_batch_csr(stack)
    assert rec.spans[1].name == "device_solve"
    assert rec.spans[1].attrs["layout"] == "per_row"


def test_shared_calls_count_shared_solves_only():
    with obs.recording() as rec:
        mp._mcr_batch_csr(_live(4))
        mp._mcr_batch_csr(_two_topologies())
        mp._mcr_batch_csr(_ring_stack(3, 16, 1, shortcuts=True))
    assert rec.counters["solve.calls"] == 3
    assert rec.counters["solve.shared_calls"] == 2
    layouts = [s.attrs["layout"] for s in rec.spans
               if s.name == "device_solve"]
    assert layouts == ["shared", "per_row", "shared"]


@pytest.mark.parametrize("name", ["live_pads", "ring_sc"])
def test_sharded_shared_stack_matches_unsharded(name):
    make, kw = SHARED[name]
    stack = make()
    dev = jax.devices()[0]
    with obs.recording() as one:
        a = mp._mcr_batch_csr(stack, **kw)
    with obs.recording() as two:
        b = mp._mcr_batch_csr(stack, devices=[dev, dev], **kw)
    np.testing.assert_array_equal(_bits(a), _bits(b))
    for rec in (one, two):
        assert rec.counters["solve.shared_calls"] == 1
    span = next(s for s in two.spans if s.name == "device_solve")
    assert span.attrs["layout"] == "shared"
