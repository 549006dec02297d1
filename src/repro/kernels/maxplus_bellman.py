"""Device-resident exact max-plus lambda-search: CSR Bellman-Ford on JAX.

The batched analysis hot path (:func:`repro.core.maxplus.mcr_batch`)
bisects a per-row lambda and asks, per probe, whether ``weights -
lam*tokens`` contains a positive cycle — a longest-path Bellman-Ford
relaxation over the whole EdgeStack.  The numpy ``"edges"`` backend runs
that host-side, one python-level relaxation round at a time; this module
executes the WHOLE search as one jitted program (the ``"csr-jit"``
backend):

  * the bisection state (lo, hi, has_cycle) and the node-major distance
    buffer live on device across all probe rounds — the distances are
    created inside the program, so nothing but the edge arrays and the
    interval bounds crosses from the host;
  * every relaxation sweep evaluates ``K`` probe lambdas per row at once
    (a broadcast axis on the edge weights).  The relaxation round count
    per sweep is pinned at the Bellman-Ford bound (~``n+1``) regardless
    of how many lambdas ride along, so one K-wide sweep replaces
    ``log2(K+1)`` binary-bisection sweeps nearly for free — sequential
    probe rounds drop from ``~log2(range/tol)`` to ``~log_{K+1}``;
  * rows whose interval already closed start their probes resolved and
    are masked out of the convergence test, so one slow row never drags
    the batch through extra relaxation rounds.

The relaxation uses an ELLPACK layout on every platform: incoming edges
of every destination node padded to the max in-degree ``d``, so the
per-round segment fold becomes a dense ``dist[ell_src] + ww`` gather and
a ``max`` over the degree axis.  No scatter anywhere; this is what XLA
vectorizes well on CPU and TPU alike (the scatter-based ``segment_max``
lowering costs several times a numpy ``reduceat`` per round on CPU).
The pack takes one of three layouts.  When the stack's rows share one
topology and differ only in weights (the subset candidates of one app),
the ELL is built once over that topology and each node carries every row
as a replica: the gather then fetches one lane-dense row of ``B*K``
distances per edge slot, where a per-row pack fetches ``B*n*d`` rows of
``K``, each padded to a full vector tile.  When the rows share a base of
edges and differ in a few more per node (the binding candidates of one
app: shared self, flow and buffer edges, per-row order edges), the base
relaxes as a shared ELL and the rest as a small per-replica ELL at the
same nodes, ``n*d1*B`` rows of ``K``.  Any other stack packs row by row.

Everything here is float64 (``jax.enable_x64(True)`` scoped to these
calls; XLA:TPU emulates it with float32 pairs): the bisection must
resolve 1e-8-class relative tolerances, which float32 intervals cannot
represent.  Host-side packing (the CSR sort, the ELL build, the path
bounds) stays in :mod:`repro.core.maxplus`; this module is pure
array-in/array-out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

NEG_INF = float("-inf")

#: Probe lambdas evaluated per relaxation sweep (the broadcast axis K).
#: Sweeps shrink the interval (K+1)x, i.e. sweep count falls log2(K+1)x,
#: while per-round gather cost grows ~linearly in K — the efficiency
#: frontier K / log2(K+1) favors small K, but K=1 forfeits the shared
#: per-sweep costs (convergence checks, cycle certificates, loop
#: dispatch).  K=3 is the measured sweet spot on CPU; accelerators with
#: wide vector units amortize larger K.
DEFAULT_K_PROBES = 3

#: Relaxation rounds per block of the probe loop: each block ends in one
#: convergence and cycle verdict.
CHECK_EVERY = 4

# ======================================================================
# the jitted device-resident bisection
# ======================================================================
def _follow(ptr, idx):
    """``ptr[idx[i, j], j]`` for every (i, j): one pointer hop per probe.

    A 1-D gather per probe column.  The equivalent
    ``take_along_axis(ptr, idx, axis=0)`` gathers row by row, and its TPU
    code generation grows with the row count: 34 s to compile at 98,304
    rows against 0.4 s for this form (v5e, ahead of time).
    """
    return jax.vmap(lambda p, i: p[i], in_axes=1, out_axes=1)(ptr, idx)


def csr_bisect(
    operands,       # (ell_src (N, d), ell_w (N, d, R), ell_t (N, d)[,
                    #  rep_src, rep_w, rep_t, each (N, d1, R)])
    lo,             # (B,) float64 sound lower bounds
    hi,             # (B,) float64 interval tops (> any finite cycle ratio)
    has_cycle,      # (B,) bool rows already known cyclic
    rel_tol,        # () float64 relative interval tolerance
    *,
    n_actors: int,
    k_probes: int = DEFAULT_K_PROBES,
    max_steps: int = 40,
    max_rounds: int = 0,       # relaxation rounds per probe; 0 -> n+1
    detect_deadlock: bool = False,
):
    """Whole-stack lambda bisection, resident on the default device.

    Returns ``(lo, hi, has_cycle, deadlocked, counts)``; the caller's
    result is ``0.5 * (lo + hi)`` where ``has_cycle`` (and ``inf``/``-inf``
    elsewhere).  ``upper`` — the per-row simple-path weight bound whose
    breach flags a pumping positive cycle — is recovered from ``hi``
    (the host passes ``hi = max(upper, lo) + 1``).  Mirrors
    :func:`repro.core.maxplus._positive_cycle_masks` exactly, with the
    K-probe broadcast axis and converged-row masking on top.

    The ELL rows are ``N`` nodes, each carrying ``R`` replicas that share
    its incoming edges and tokens but not their weights: row ``b`` of the
    stack is replica ``b % R`` of nodes ``(b // R) * n`` onwards.  A
    per-row pack is ``R = 1, N = B*n``; a stack whose rows share one
    topology packs as ``R = B, N = n``.  Distances are node-major,
    ``(N, R*k)`` with column ``r*k + j`` for replica ``r``'s probe ``j``,
    so a relaxation gathers one row of ``R*k`` distances per edge slot.
    A split pack (``R = B, N = n``) adds three per-replica operands:
    replica ``r``'s own incoming edges at node ``v`` sit in
    ``rep_*[v, :, r]``, and each gathers the ``k`` distances of its
    source's replica ``r``.  Every round takes the max over both groups,
    and the witness looks at the shared slots first, then the
    per-replica ones.

    ``counts`` is an int32 ``(3,)`` array that the loops carry beside the
    search and that feeds nothing back into it: the outer bisection steps;
    the probe loop's blocks of ``CHECK_EVERY`` relaxation rounds, summed
    over every step and the deadlock probe; and, summed over the same
    blocks, the (row, probe) pairs not yet resolved when the block began,
    counting rows with a finite edge only (all--inf pad rows left out).
    """
    ell_src, ell_w, ell_t = operands[:3]
    rep = operands[3:]                     # split layout only
    b = lo.shape[0]
    n_nodes, _, reps = ell_w.shape
    groups = b // reps                     # = n_nodes // n_actors
    rounds = max_rounds if max_rounds else n_actors + 1
    n_blocks = -(-rounds // CHECK_EVERY)
    n_doublings = max(1, (n_actors + 1).bit_length())
    upper = hi - 1.0                       # host invariant: hi = upper' + 1
    ids = jnp.arange(n_nodes, dtype=jnp.int32)[:, None]
    slots = ell_src.shape[1] + (rep[0].shape[1] if rep else 0)
    slot = jnp.arange(slots, dtype=jnp.int32)
    real = jnp.isfinite(ell_w).reshape(groups, -1, reps).any(axis=1)
    if rep:
        real = real | jnp.isfinite(rep[1]).any(axis=(0, 1))  # groups == 1
    real = real.reshape(b, 1)

    def per_row(x, k):
        """(N, R*k) node flags -> (B, k): any over each row's nodes."""
        return x.reshape(groups, n_actors, -1).any(axis=1).reshape(b, k)

    def make_round(lams):
        # (N, 1, R*k) probe weights fold into the gathered candidates;
        # column r*k + j takes replica r's weights, hence the repeats
        k = lams.shape[1]
        lam_col = jnp.repeat(lams.reshape(groups, reps * k), n_actors,
                             axis=0)[:, None, :]
        w_col = jnp.repeat(ell_w, k, axis=2)

        def cand(dist):
            return dist[ell_src] + (w_col - lam_col * ell_t[:, :, None])

        if rep:
            # split layout: replica r's own edges gather the k distances
            # of its source's replica r, rows of dist viewed as (N*R, k)
            rep_src, rep_w, rep_t = (jnp.repeat(a, k, axis=2) for a in rep)
            rep_row = rep[0] * reps + jnp.arange(reps, dtype=jnp.int32)

            def cand_rep(dist):
                c = dist.reshape(-1, k)[rep_row].reshape(rep_src.shape)
                return c + (rep_w - lam_col * rep_t)

        def best_of(dist):
            best = cand(dist).max(axis=1)
            if rep:
                best = jnp.maximum(best, cand_rep(dist).max(axis=1))
            return best

        def witness(dist):
            # slots in column order: the shared ones, then the replica's
            c = cand(dist)
            if rep:
                c = jnp.concatenate([c, cand_rep(dist)], axis=1)
            amax = c.argmax(axis=1)                         # (N, R*k)
            # one-hot select of the argmax source: the same values as
            # take_along_axis, whose per-row gather takes the TPU
            # compiler ~35 s at 1e5 rows (this form: ~1 s)
            hit = slot[None, :, None] == amax[:, None, :]
            src = ell_src[:, :, None]
            if rep:
                src = jnp.concatenate([jnp.broadcast_to(
                    src, (*ell_src.shape, reps * k)), rep_src], axis=1)
            psrc = jnp.where(hit, src, 0).sum(axis=1)
            return c.max(axis=1), psrc

        return best_of, witness

    def probe(lams, active):
        """(B, k) positive-cycle verdicts at per-row probe lambdas.

        Longest-path Bellman-Ford with three resolution rules, applied
        every ``CHECK_EVERY`` rounds: a probe with no improving node has
        settled (no positive cycle — the fixpoint is monotone); a node
        past the simple-path bound can only have been pumped by a
        positive cycle; and — the rule the numpy backend cannot afford —
        a cycle in the *tight-edge graph* certifies a (>= 0)-weight
        cycle right now.  Tight edges point each still-improvable node
        ``v`` (``best(v) >= dist(v)``) at an argmax predecessor ``p``
        over the same distance snapshot, so around any cycle of them
        ``sum(w) = sum(best(v_next) - dist(v)) >= sum(dist(v_next) -
        dist(v)) = 0``.  (The boundary probe this conflates with
        "positive" sits within the bisection tolerance by definition.)
        Pointer doubling finds tight-edge cycles in log2(n) gathers, so
        positive probes resolve in O(path + cycle hops) rounds instead
        of pumping distances toward the bound for O(n) rounds — the
        round count that actually gates every sweep.  The relaxation
        rounds between checks stay pure gather/max (no argmax, no
        bookkeeping), which is what keeps them at memory-bandwidth cost.
        """
        k = lams.shape[1]
        best_of, witness = make_round(lams)
        over_col = jnp.repeat(upper.reshape(groups, reps), k, axis=1)
        over_col = over_col[:, None, :] + 1.0                # (G, 1, R*k)
        resolved0 = jnp.broadcast_to(~active[:, None], (b, k))
        positive0 = jnp.zeros((b, k), dtype=bool)
        dist = jnp.zeros((n_nodes, reps * k), dtype=lo.dtype)

        def cond(carry):
            _, resolved, _, blk, _ = carry
            return (blk < n_blocks) & ~resolved.all()

        def body(carry):
            dist, resolved, positive, blk, live = carry
            live = live + jnp.sum(~resolved & real, dtype=jnp.int32)
            dist = jax.lax.fori_loop(
                0, CHECK_EVERY - 1,
                lambda _, d: jnp.maximum(d, best_of(d)), dist,
            )
            # the block's last round doubles as the verdict pass: its
            # candidate fold is computed once with an argmax witness, so
            # the checks cost one argmax + log2(n) pointer hops on top of
            # the relaxation the round does anyway
            best, psrc = witness(dist)
            # once a round improves nothing, no later round can
            improving = per_row(best > dist + 1e-12, k)
            # tight-edge parents: only nodes that can still match or beat
            # their pre-round distance join the cycle-candidate graph
            par = jnp.where(best >= dist, psrc, ids)
            dist = jnp.maximum(dist, best)
            over = (dist.reshape(groups, n_actors, -1) > over_col).any(axis=1)
            over = over.reshape(b, k)
            anc = par
            for _ in range(n_doublings):
                anc = _follow(anc, anc)
            cyc = per_row(_follow(par, anc) != anc, k)
            positive = positive | ((over | cyc) & ~resolved)
            resolved = resolved | over | cyc | ~improving
            return dist, resolved, positive, blk + 1, live

        _, resolved, positive, blk, live = jax.lax.while_loop(
            cond, body, (dist, resolved0, positive0, 0, jnp.int32(0))
        )
        # probes still improving after n+1 rounds contain a positive cycle
        return positive | ~resolved, blk.astype(jnp.int32), live

    deadlocked = jnp.zeros(b, dtype=bool)
    blocks = live = jnp.int32(0)
    if detect_deadlock:
        # any cycle with >= 1 token has ratio <= upper < hi, so a positive
        # cycle AT lam = hi can only be a zero-token (deadlock) cycle with
        # positive weight sum — always the case for tau > 0 graphs
        pos, blocks, live = probe(hi[:, None], jnp.ones(b, dtype=bool))
        deadlocked = pos[:, 0]

    frac = jnp.arange(1, k_probes + 1, dtype=lo.dtype) / (k_probes + 1)

    def outer_cond(carry):
        lo, hi, _, step, _, _ = carry
        tol = rel_tol * jnp.maximum(1.0, jnp.abs(hi))
        return (step < max_steps) & ((hi - lo) > tol).any()

    def outer_body(carry):
        lo, hi, has_cycle, step, blocks, live = carry
        tol = rel_tol * jnp.maximum(1.0, jnp.abs(hi))
        active = ((hi - lo) > tol) & ~deadlocked
        lams = lo[:, None] + (hi - lo)[:, None] * frac[None, :]  # ascending
        positive, blk, blk_live = probe(lams, active)
        # positives form a prefix of the ascending probes (positive iff
        # lam < rho); the count locates rho in (lams[c-1], lams[c]]
        c = jnp.sum(positive & active[:, None], axis=1)
        pick = lambda idx: jnp.take_along_axis(
            lams, jnp.clip(idx, 0, k_probes - 1)[:, None], axis=1
        )[:, 0]
        lo = jnp.where(active & (c > 0), pick(c - 1), lo)
        hi = jnp.where(active & (c < k_probes), pick(c), hi)
        has_cycle = has_cycle | (active & (c > 0))
        return lo, hi, has_cycle, step + 1, blocks + blk, live + blk_live

    lo, hi, has_cycle, step, blocks, live = jax.lax.while_loop(
        outer_cond, outer_body, (lo, hi, has_cycle, 0, blocks, live)
    )
    counts = jnp.stack([step.astype(jnp.int32), blocks, live])
    return lo, hi, has_cycle, deadlocked, counts


_csr_bisect = jax.jit(
    csr_bisect,
    static_argnames=(
        "n_actors", "k_probes", "max_steps", "max_rounds",
        "detect_deadlock",
    ),
)


def _dispatch_bisect(
    operands, lo, hi, has_cycle,
    *,
    n_actors: int,
    rel_tol: float,
    k_probes: int,
    max_steps: int,
    max_rounds: int,
    detect_deadlock: bool,
):
    """Enqueue the bisection (inside a ``jax.enable_x64`` scope).

    Returns the five result arrays as jax arrays on the default device,
    WITHOUT forcing them to host: the caller gathers with ``np.asarray``.
    """
    def put(x, dtype):
        # numpy casts on the host: ``jnp.asarray(x, dtype)`` would run a
        # jitted convert on the device, one more compile per new shape
        return jnp.asarray(np.asarray(x, dtype=dtype))

    # (src, w, t) of the ELL, then of the per-replica ELL when split
    ops_dev = tuple(put(x, np.int32 if i % 3 == 0 else np.float64)
                    for i, x in enumerate(operands))
    return _csr_bisect(
        ops_dev,
        put(lo, np.float64),
        put(hi, np.float64),
        put(has_cycle, bool),
        put(rel_tol, np.float64),
        n_actors=n_actors,
        k_probes=k_probes,
        max_steps=max_steps,
        max_rounds=max_rounds,
        detect_deadlock=detect_deadlock,
    )


def _tally(counts, operands, b: int, k_probes: int) -> dict:
    """A solve's loop counts, pulled to the host, as solve counters.

    ``steps``: bisection steps; ``rounds``: relaxation rounds, one probe
    loop after another; ``probe_rounds``: (row, probe) pairs those rounds
    relaxed, over the rows with a finite edge; ``live_probe_rounds``: the
    ones among them not yet resolved; ``relaxations``: single edge
    relaxations, ``(B*n) * d * K`` a round, pad rows and slots included,
    with ``d = d0 + d1`` in the split layout.
    """
    steps, blocks, live = (int(x) for x in np.asarray(counts))
    weights = operands[1::3]               # ell_w, and rep_w when split
    nodes, _, reps = np.shape(weights[0])
    d = sum(np.shape(w)[1] for w in weights)
    real = np.zeros((b // reps, reps), dtype=bool)
    for w in weights:
        real |= np.isfinite(w).reshape(b // reps, -1, reps).any(axis=1)
    rows = int(real.sum())
    rounds = CHECK_EVERY * blocks
    return {
        "steps": steps,
        "rounds": rounds,
        "probe_rounds": rounds * rows * k_probes,
        "live_probe_rounds": CHECK_EVERY * live,
        "relaxations": rounds * nodes * reps * d * k_probes,
    }


def _layout(operands) -> str:
    """A solve's layout, from its packed operands: ``"per_row"`` when it
    packs row by row (one replica per ELL node), else ``"split"`` when it
    carries per-replica operands, else ``"shared"``."""
    if np.shape(operands[1])[2] == 1:
        return "per_row"
    if len(operands) > 3:
        return "split"
    return "shared"


def _record(span, tally: dict) -> None:
    """Adds one solve's counters (``solve.calls``, ``solve.shared_calls``,
    ``solve.split_calls`` and ``solve.<key>`` of :func:`_tally`) to the
    attached recorder and puts them on the solve's ``device_solve`` span,
    beside its ``layout``."""
    span.attrs.update(tally)
    obs.count("solve.calls")
    obs.count("solve.shared_calls", int(span.attrs["layout"] == "shared"))
    obs.count("solve.split_calls", int(span.attrs["layout"] == "split"))
    for key, n in tally.items():
        obs.count(f"solve.{key}", n)


def mcr_bisect_device(
    operands, lo, hi, has_cycle,
    *,
    n_actors: int,
    rel_tol: float,
    k_probes: int = DEFAULT_K_PROBES,
    max_steps: int = 40,
    max_rounds: int = 0,
    detect_deadlock: bool = False,
):
    """Host-facing entry: numpy CSR/ELL arrays in, numpy results out.

    ``operands`` is ``(ell_src, ell_w, ell_t)``, shaped ``(N, d)``,
    ``(N, d, R)`` and ``(N, d)``, and in the split layout three
    per-replica arrays more, each ``(N, d1, R)`` (:func:`csr_bisect`).
    Scopes ``jax.enable_x64(True)`` around conversion, tracing and
    execution so the bisection runs in float64 without flipping the
    process-global jax precision (the Pallas semiring kernels stay
    float32).  The solve runs on the default device.

    The call is the program's ``device_solve`` span; its ``layout`` attr
    says how the rows were packed (:func:`_layout`).  While a recorder is
    attached (:func:`repro.obs.recording`) the solve's loop counts come
    back from the device and are recorded (:func:`_record`); else they
    never leave it.
    """
    with obs.span("device_solve", layout=_layout(operands)) as sp, \
            jax.enable_x64(True):
        out = _dispatch_bisect(
            operands, lo, hi, has_cycle,
            n_actors=n_actors, rel_tol=rel_tol, k_probes=k_probes,
            max_steps=max_steps, max_rounds=max_rounds,
            detect_deadlock=detect_deadlock,
        )
        lo, hi, has_cycle, deadlocked = (np.asarray(x) for x in out[:4])
        if obs.recorder() is not None:
            _record(sp, _tally(out[4], operands, len(lo), k_probes))
    return lo, hi, has_cycle, deadlocked

