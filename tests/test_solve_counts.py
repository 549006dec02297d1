"""The loop counts that ``csr_bisect`` carries beside its search, and the
solve counters built from them: they follow the rows that are still open,
respect the round cap, and leave every period bit-identical."""

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

K = kbell.DEFAULT_K_PROBES


def counts(stack, *, lo=None, hi=None, max_rounds=0, max_steps=20):
    """(steps, blocks, live) of one jitted solve of ``stack``."""
    operands, lo0, hi0, has_cycle = mp._pack_csr_chunk(stack, None)
    lo = lo0 if lo is None else lo
    hi = hi0 if hi is None else hi
    with jax.enable_x64(True):
        out = kbell._dispatch_bisect(
            operands, lo, hi, has_cycle, n_actors=stack.n_actors,
            rel_tol=1e-8, k_probes=K, max_steps=max_steps,
            max_rounds=max_rounds, detect_deadlock=False)
        return tuple(int(x) for x in np.asarray(out[4]))


def live_graph(seed=11, n=9):
    return random_live_sdfg(np.random.default_rng(seed), n)


def test_identical_rows_same_steps_and_blocks_b_times_live():
    g = live_graph()
    steps1, blocks1, live1 = counts(stack_graphs([g]))
    b = 5
    steps, blocks, live = counts(stack_graphs([g] * b))
    assert (steps, blocks) == (steps1, blocks1)
    assert live == b * live1
    assert steps1 > 0 and blocks1 >= steps1


def test_live_bounded_by_blocks_rows_probes():
    rng = np.random.default_rng(4)
    graphs = [random_live_sdfg(rng, int(rng.integers(3, 14)))
              for _ in range(6)]
    steps, blocks, live = counts(stack_graphs(graphs))
    assert 0 < live <= blocks * len(graphs) * K


def test_closed_row_adds_no_live():
    g = live_graph()
    alone = counts(stack_graphs([g]))
    pair = stack_graphs([g, live_graph(seed=12)])
    _, lo, hi, _ = mp._pack_csr_chunk(pair, None)
    lo, hi = lo.copy(), hi.copy()
    lo[1] = hi[1]                            # row 1's interval is closed
    assert counts(pair, lo=lo, hi=hi) == alone


@pytest.mark.parametrize("max_rounds", [4, 8])
def test_max_rounds_caps_blocks_per_probe(max_rounds):
    ring = _ring_stack(2, 40, 3, shortcuts=False)
    per_probe = -(-max_rounds // kbell.CHECK_EVERY)
    steps, blocks, _ = counts(ring, max_rounds=max_rounds)
    assert steps > 0 and blocks <= steps * per_probe
    if max_rounds <= kbell.CHECK_EVERY:
        assert blocks == steps               # every step runs one block
    steps0, blocks0, _ = counts(ring)
    assert blocks0 > steps0 * per_probe      # uncapped, rings take longer


def test_recorded_counters_follow_the_loop_counts():
    stack = _ring_stack(3, 24, 5, shortcuts=True)
    off = mp._mcr_batch_csr(stack)
    with obs.recording() as rec:
        on = mp._mcr_batch_csr(stack)
    np.testing.assert_array_equal(on, off)
    c = rec.counters
    (ell_src, _, _), lo, _, _ = mp._pack_csr_chunk(stack, None)
    steps_cap = max(4, int(math.ceil(80 / math.log2(K + 1))) + 1)
    steps, blocks, live = counts(stack, max_steps=steps_cap)
    rounds = kbell.CHECK_EVERY * blocks
    # the ring's rows share one topology: the relaxations are still
    # counted over every row's nodes, (B*n) * d * K a round
    assert c == {
        "solve.calls": 1,
        "solve.shared_calls": 1,
        "solve.split_calls": 0,
        "solve.steps": steps,
        "solve.rounds": rounds,
        "solve.probe_rounds": rounds * len(lo) * K,
        "solve.live_probe_rounds": kbell.CHECK_EVERY * live,
        "solve.relaxations": (rounds * len(lo) * stack.n_actors
                              * ell_src.shape[1] * K),
    }
    assert [s.name for s in rec.spans] == ["pack", "device_solve", "solve"]
    # the call's own tally and layout ride on its device_solve span
    assert rec.spans[1].attrs == {
        "layout": "shared",
        **{k[len("solve."):]: v for k, v in c.items()
           if k not in ("solve.calls", "solve.shared_calls",
                        "solve.split_calls")}}


def _split_stack(b=4, n=12):
    """Rings of ``n`` actors with self-loops, the same in every row, and
    one more in-edge a node that differs from row to row: the base of a
    split pack and its per-replica edges."""
    rng = np.random.default_rng(17)
    ring = np.arange(n)
    src = np.concatenate([np.broadcast_to(ring, (b, n)),
                          np.broadcast_to(ring, (b, n)),
                          np.argsort(rng.random((b, n)), axis=1)], axis=1)
    dst = np.concatenate([np.broadcast_to(ring, (b, n)),
                          np.broadcast_to((ring + 1) % n, (b, n)),
                          np.broadcast_to(ring, (b, n))], axis=1)
    tokens = np.concatenate([np.ones((b, n), int),
                             np.broadcast_to(ring == n - 1, (b, n)),
                             np.ones((b, n), int)], axis=1).astype(np.int64)
    return mp.EdgeStack(n_actors=n, src=src, dst=dst, tokens=tokens,
                        weights=rng.uniform(0.5, 2.0, src.shape))


def test_split_counters_count_both_groups():
    stack = _split_stack()
    operands, lo, _, _ = mp._pack_csr_chunk(stack, None)
    assert len(operands) == 6
    d0, d1 = operands[1].shape[1], operands[4].shape[1]
    steps_cap = max(4, int(math.ceil(80 / math.log2(K + 1))) + 1)
    steps, blocks, live = counts(stack, max_steps=steps_cap)
    with obs.recording() as rec:
        mp._mcr_batch_csr(stack)
    rounds = kbell.CHECK_EVERY * blocks
    assert rec.counters == {
        "solve.calls": 1,
        "solve.shared_calls": 0,
        "solve.split_calls": 1,
        "solve.steps": steps,
        "solve.rounds": rounds,
        "solve.probe_rounds": rounds * len(lo) * K,
        "solve.live_probe_rounds": kbell.CHECK_EVERY * live,
        "solve.relaxations": (rounds * stack.n_actors * (d0 + d1)
                              * len(lo) * K),
    }
    span = next(s for s in rec.spans if s.name == "device_solve")
    assert span.attrs["layout"] == "split"


def test_layout_of_a_solve_over_its_chunks():
    """A sharded solve is as shared as its least shared chunk."""
    per_row = mp._pack_per_row(_split_stack(), None)[0]
    shared = mp._pack_csr_chunk(_ring_stack(3, 16, 1, shortcuts=True),
                                None)[0]
    split = mp._pack_csr_chunk(_split_stack(), None)[0]
    assert kbell._layout([shared, shared]) == "shared"
    assert kbell._layout([split, shared]) == "split"
    assert kbell._layout([split, split]) == "split"
    assert kbell._layout([split, per_row]) == "per_row"
    assert kbell._layout([per_row]) == "per_row"


def test_sharded_split_solve_counts_one_split_call():
    stack = _split_stack(b=6)
    dev = jax.devices()[0]
    with obs.recording() as one:
        a = mp._mcr_batch_csr(stack)
    with obs.recording() as two:
        b = mp._mcr_batch_csr(stack, devices=[dev, dev])
    np.testing.assert_array_equal(a, b)
    for rec in (one, two):
        assert rec.counters["solve.calls"] == 1
        assert rec.counters["solve.split_calls"] == 1
        assert rec.counters["solve.shared_calls"] == 0
        span = next(s for s in rec.spans if s.name == "device_solve")
        assert span.attrs["layout"] == "split"


def _pad(stack, rows):
    """``stack`` with ``rows`` all--inf rows appended, as a sharded chunk
    or a bucket-padded batch carries them."""
    e = stack.n_edges
    cat = lambda a, fill: np.concatenate([a, np.full((rows, e), fill,
                                                     dtype=a.dtype)])
    return mp.EdgeStack(n_actors=stack.n_actors, src=cat(stack.src, 0),
                        dst=cat(stack.dst, 0), tokens=cat(stack.tokens, 1),
                        weights=cat(stack.weights, -math.inf))


def test_pad_rows_add_no_live_and_no_probe_rounds():
    stack = stack_graphs([live_graph()])
    padded = _pad(stack, 2)
    assert counts(padded)[2] == counts(stack)[2]
    with obs.recording() as rec:
        mp._mcr_batch_csr(padded)
    c = rec.counters
    assert c["solve.probe_rounds"] == c["solve.rounds"] * 1 * K


def test_sharded_counters_add_over_chunks():
    """Chunks run side by side: a sharded solve's steps and rounds are its
    longest chunk's, its pairs and relaxations add over the chunks."""
    rng = np.random.default_rng(9)
    stack = stack_graphs([random_live_sdfg(rng, 8) for _ in range(4)])
    dev = jax.devices()[0]
    with obs.recording() as one:
        a = mp._mcr_batch_csr(stack)
    with obs.recording() as two:
        b = mp._mcr_batch_csr(stack, devices=[dev, dev])
    np.testing.assert_array_equal(a, b)
    assert two.counters["solve.calls"] == 1
    halves = []
    for sl in (slice(0, 2), slice(2, 4)):
        with obs.recording() as rec:
            mp._mcr_batch_csr(mp.EdgeStack(
                n_actors=stack.n_actors, src=stack.src[sl], dst=stack.dst[sl],
                tokens=stack.tokens[sl], weights=stack.weights[sl]))
        halves.append(rec.counters)
    for key in ("solve.steps", "solve.rounds"):
        assert two.counters[key] == max(halves[0][key], halves[1][key]), key
    for key in ("solve.probe_rounds", "solve.live_probe_rounds",
                "solve.relaxations"):
        assert two.counters[key] == halves[0][key] + halves[1][key], key
    assert one.counters["solve.calls"] == 1


def _deadlock_stack():
    live = SDFG(n_actors=3, exec_time=np.array([1.0, 2.0, 3.0]),
                channels=[Channel(0, 1, 0, 1.0), Channel(1, 2, 0, 1.0),
                          Channel(2, 0, 1, 1.0)])
    dead = SDFG(n_actors=3, exec_time=np.array([1.0, 2.0, 3.0]),
                channels=[Channel(0, 1, 0, 1.0), Channel(1, 0, 0, 1.0),
                          Channel(2, 2, 1, 1.0)])
    return stack_graphs([live, dead, live])


def _live_stack(seed, count, lo_n, hi_n):
    rng = np.random.default_rng(seed)
    return stack_graphs([random_live_sdfg(rng, int(rng.integers(lo_n, hi_n)))
                         for _ in range(count)])


#: periods of the jitted solve before the loop counts rode along, as
#: float.hex; the counts feed nothing back, so every bit must stay
GOLDEN = {
    "live77": (lambda: _live_stack(77, 4, 4, 12), {"rel_tol": 1e-9}, [
        "0x1.108a937044c98p+3", "0x1.32857161d70f2p+4",
        "0x1.02b182d2b0062p+5", "0x1.58fb9c29e3afap+4"]),
    "live0": (lambda: _live_stack(0, 5, 3, 14), {"rel_tol": 1e-9}, [
        "0x1.1d93db3fbe2cdp+5", "0x1.d7c8c625279d0p+3",
        "0x1.a24efb211319fp+4", "0x1.0e1ad9176055ep+5",
        "0x1.83e2f1c055722p+3"]),
    "live1": (lambda: _live_stack(1, 5, 3, 14), {"rel_tol": 1e-9}, [
        "0x1.88acb7a8f32f6p+4", "0x1.28f25c6e6ada2p+5",
        "0x1.fd3f976612c0ap+3", "0x1.9d4cc75991d9dp+4",
        "0x1.ed44b4d64cc88p+3"]),
    "live2": (lambda: _live_stack(2, 5, 3, 14), {"rel_tol": 1e-9}, [
        "0x1.bd7772a5525dap+4", "0x1.32c6045b3f1e6p+5",
        "0x1.1f0bc771b5ae4p+4", "0x1.c71e5c6aaac62p+3",
        "0x1.00b5c046e0168p+3"]),
    "deadlock": (_deadlock_stack, {"detect_deadlock": True}, [
        "0x1.7ffffffa00000p+2", "inf", "0x1.7ffffffa00000p+2"]),
    "ring": (lambda: _ring_stack(4, 24, 3, shortcuts=False), {}, [
        "0x1.c45e7f7bf5656p+4", "0x1.01e8abb7ed57ap+5",
        "0x1.0008e82e6565ep+5", "0x1.db5b6b660d692p+4"]),
    "ring_sc": (lambda: _ring_stack(4, 24, 3, shortcuts=True), {}, [
        "0x1.c45e7f6f263b0p+4", "0x1.01e8abb876c4cp+5",
        "0x1.0008e820bf072p+5", "0x1.db5b6b69f04b2p+4"]),
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_periods_bit_identical_with_counts(name):
    make, kw, want = GOLDEN[name]
    stack = make()
    off = mp._mcr_batch_csr(stack, **kw)
    with obs.recording():
        on = mp._mcr_batch_csr(stack, **kw)
    assert [float(x).hex() for x in off] == want
    assert [float(x).hex() for x in on] == want
