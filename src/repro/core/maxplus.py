"""Max-Plus Algebra performance analysis (paper §3.2, §4.4).

Throughput of a (hardware-aware) SDFG = 1 / maximum cycle mean of its
max-plus matrix (Eq. 6).  For a timed event graph with markings ``m`` and
edge weights ``w = tau[dst] + delay`` this is the *maximum cycle ratio*

    rho_max = max over cycles C of  sum_{e in C} w(e) / sum_{e in C} m(e).

Per-graph evaluators (cross-validated in tests):

  * :func:`mcr_howard`      — Howard's policy iteration (exact, fast; default)
  * :func:`mcr_binary_search` — lambda-search + vectorized Bellman-Ford
  * :func:`mcm_power_iteration` — t_k = T (x) t_{k-1} on the explicit max-plus
    matrix ``T = A0* (x) A1`` (Eq. 4), executed with the Pallas
    ``maxplus_matmul`` kernel (VPU semiring matmul; jnp oracle on CPU).

Batched evaluator (the design-space-exploration hot path):

  * :func:`mcr_batch` — lambda-search + Bellman-Ford over an
    :class:`EdgeStack`, a *stack* of edge-weight arrays (one row per
    candidate binding / hardware config / static order).  The whole stack
    bisects together: every Bellman-Ford relaxation touches all candidates
    in one segment-max over flat arrays, so interpreter overhead is paid
    once per sweep instead of once per candidate per sweep.  Two backends:
    ``"edges"`` (float64 numpy segment-max — exact, the CPU default) and
    ``"dense"`` (max-plus matrix squaring through the Pallas
    ``maxplus_bmm`` semiring kernel on TPU / jnp oracle elsewhere —
    float32, looser tolerance, wins at large batch x actor counts).

Batched Eq.-4 evolution (the self-timed engine's start-time path):

  * :func:`maxplus_matrix_batch` — (B, n, n) matrices ``T = A0* (x) A1``
    with the Kleene star computed by repeated ``maxplus_bmm`` squaring.
  * :func:`evolve_batch` — iterate ``x(k) = T (x) x(k-1)`` for the whole
    batch through ``maxplus_bmv``; returns steady-state start vectors and
    a growth-rate period estimate (exact periods come from `mcr_batch`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import numpy as np

from .. import obs
from .sdfg import SDFG

NEG_INF = -math.inf


# ======================================================================
# Howard's policy iteration for Maximum Cycle Ratio
# ======================================================================
def mcr_howard(g: SDFG, *, eps: float = 1e-9, max_iter: int = 10_000) -> float:
    """Exact maximum cycle ratio via Howard's algorithm.

    Returns ``inf`` for a deadlocked graph (zero-token cycle) and ``-inf``
    for a graph with no cycles at all (throughput unbounded by the graph).
    """
    src, dst, w, m = g.edges_arrays()
    n = g.n_actors
    ne = src.size
    if ne == 0:
        return NEG_INF

    # the first policy takes each node's first outgoing edge; nodes with no
    # outgoing edge can't be on a cycle; give them a virtual self-loop of
    # ratio -inf by excluding them from policies.
    edge_ids = np.arange(ne, dtype=np.int64)
    first = np.full(n, ne, dtype=np.int64)
    np.minimum.at(first, src, edge_ids)
    has_out = first < ne
    policy = np.where(has_out, first, -1)

    lam = np.full(n, NEG_INF)
    u = np.zeros(n)

    for _ in range(max_iter):
        # ---- policy evaluation -------------------------------------
        lam, u, dead = _evaluate_policy(n, policy, src, dst, w, m, has_out)
        if dead:
            return math.inf
        # ---- policy improvement ------------------------------------
        # an edge improves its source when it leads to a higher ratio, or
        # to the same ratio with a higher bias; lam and u are fixed here,
        # so each node takes its last improving edge in edge order
        lx, ly = lam[src], lam[dst]
        with np.errstate(invalid="ignore"):
            higher = ly > lx + eps
            tied = np.abs(ly - lx) <= eps
            cand = w - lx * m + u[dst]
        better = (ly != NEG_INF) & (
            higher | (tied & (cand > u[src] + eps)))
        if not better.any():
            break
        last = np.full(n, -1, dtype=np.int64)
        np.maximum.at(last, src[better], edge_ids[better])
        policy = np.where(last >= 0, last, policy)
    finite = lam[np.isfinite(lam)]
    return float(finite.max()) if finite.size else NEG_INF


def _evaluate_policy(n, policy, src, dst, w, m, has_out):
    """Evaluate a policy (functional graph): per-node cycle ratio + bias."""
    # the walk runs on Python lists: indexing numpy arrays one scalar at a
    # time costs more than the arithmetic, which is float64 either way
    succ = dst[policy].tolist()
    pw = w[policy].tolist()
    pm = m[policy].tolist()
    live = has_out.tolist()
    lam = [NEG_INF] * n
    u = [0.0] * n
    color = [0] * n  # 0 white 1 on-stack 2 done

    for start in range(n):
        if color[start] != 0 or not live[start]:
            color[start] = 2
            continue
        path: list[int] = []
        v = start
        while color[v] == 0:
            color[v] = 1
            path.append(v)
            v = succ[v]
            if not live[v]:
                break
        if color[v] == 1:
            # found a new cycle: v .. path[-1]
            cyc = path[path.index(v):]
            wsum, msum = 0.0, 0
            for x in cyc:       # left to right, as the cycle is walked
                wsum += pw[x]
                msum += pm[x]
            if msum == 0:
                return np.array(lam), np.array(u), True
            ratio = wsum / msum
            for x in cyc:
                lam[x] = ratio
            # bias along the cycle: u(x) = w̄(x) + u(pi(x)), anchored u(v)=0;
            # walk the cycle backwards so each successor is resolved first
            u[v] = 0.0
            for x in reversed(cyc[1:]):
                u[x] = pw[x] - ratio * pm[x] + u[succ[x]]
        # resolve tree part (suffix of `path` before the cycle / known node)
        for x in reversed(path):
            if lam[x] != NEG_INF:
                continue
            y = succ[x]
            if lam[y] == NEG_INF:
                u[x] = 0.0  # leads nowhere cyclic
            else:
                lam[x] = lam[y]
                u[x] = pw[x] - lam[x] * pm[x] + u[y]
        for x in path:
            color[x] = 2
        color[v] = 2
    return np.array(lam), np.array(u), False


# ======================================================================
# Binary search + vectorized Bellman-Ford (independent cross-check)
# ======================================================================
def mcr_binary_search(
    g: SDFG, *, tol: float = 1e-6, lo: float = 0.0, hi: Optional[float] = None
) -> float:
    """MCR via lambda-search: a positive cycle in weights ``w - lam*m``
    exists iff lam < rho_max.  Longest-path Bellman-Ford, fully vectorized.
    """
    src, dst, w, m = g.edges_arrays()
    n = g.n_actors
    if hi is None:
        hi = float(w.sum()) + 1.0  # any cycle ratio is below total weight

    def has_positive_cycle(lam: float) -> bool:
        ww = w - lam * m
        dist = np.zeros(n)
        for _ in range(n):
            cand = dist[src] + ww
            new = dist.copy()
            np.maximum.at(new, dst, cand)
            new = np.maximum(new, dist)
            if np.allclose(new, dist, rtol=0, atol=1e-12):
                return False
            dist = new
        return True

    if not has_positive_cycle(lo + tol):
        return lo
    while hi - lo > tol:
        mid = 0.5 * (lo + hi)
        if has_positive_cycle(mid):
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


# ======================================================================
# Explicit max-plus matrix T = A0* (x) A1 and power iteration (Eq. 4)
# ======================================================================
def maxplus_matrix(g: SDFG) -> np.ndarray:
    """Build T with t_k = T (x) t_{k-1}.

    Dependencies within an iteration (0-token edges) are closed transitively
    over the acyclic 0-token subgraph (Kleene star A0*); dependencies across
    iterations (>=1-token edges) contribute A1.  Markings > 1 relax the
    dependency further into the past and — for a conservative (upper-bound
    period, lower-bound throughput) T — are kept as if 1 token; the exact
    multi-token analysis is done by :func:`mcr_howard`.
    """
    src, dst, w, m = g.edges_arrays()
    n = g.n_actors
    T = np.full((n, n), NEG_INF)

    # A1 edges: j fires after i's previous firing + w
    one = m >= 1
    for s, d, ww in zip(src[one], dst[one], w[one]):
        T[int(d), int(s)] = max(T[int(d), int(s)], float(ww))

    # longest-path closure over 0-token edges, topological order
    zero = m == 0
    z_src, z_dst, z_w = src[zero], dst[zero], w[zero]
    indeg = np.zeros(n, dtype=np.int64)
    adj: list[list[tuple[int, float]]] = [[] for _ in range(n)]
    for s, d, ww in zip(z_src, z_dst, z_w):
        adj[int(s)].append((int(d), float(ww)))
        indeg[int(d)] += 1
    topo: list[int] = [i for i in range(n) if indeg[i] == 0]
    head = 0
    while head < len(topo):
        x = topo[head]
        head += 1
        for y, _ in adj[x]:
            indeg[y] -= 1
            if indeg[y] == 0:
                topo.append(y)
    assert len(topo) == n, "0-token subgraph must be acyclic (liveness)"

    # propagate rows of T along zero edges: T[y,:] >= T[x,:] + w(x->y)
    for x in topo:
        row = T[x]
        for y, ww in adj[x]:
            np.maximum(T[y], row + ww, out=T[y])
    return T


def mcm_power_iteration(
    T: np.ndarray, *, iters: int = 200, use_kernel: bool = True
) -> float:
    """Estimate the max-plus eigenvalue (MCM) of T by power iteration.

    Uses the Pallas ``maxplus_matmul`` kernel when available; falls back to
    the pure-jnp oracle.  For irreducible T the growth rate of
    ``x_k = T (x) x_{k-1}`` converges to the MCM.
    """
    n = T.shape[0]
    if use_kernel:
        try:
            from repro.kernels import ops as kops

            matvec = kops.maxplus_matvec
        except Exception:  # pragma: no cover - kernel import fallback
            matvec = None
    else:
        matvec = None

    x = np.zeros(n)
    warm = max(4, iters // 2)
    x0_at_warm = None
    for k in range(iters):
        if matvec is not None:
            x = np.asarray(matvec(T, x))
        else:
            x = np.max(T + x[None, :], axis=1)
        # renormalize to avoid drift; track growth of the max component
        mx = x.max()
        if not np.isfinite(mx):
            return float(mx)
        if k == warm:
            x0_at_warm = mx
        if mx > 1e12:
            x -= mx
            if x0_at_warm is not None:
                x0_at_warm -= mx
    if x0_at_warm is None:  # pragma: no cover
        return float("nan")
    return float((x.max() - x0_at_warm) / (iters - 1 - warm))


# ======================================================================
# Batched analysis: lambda-search over a stack of edge-weight arrays
# ======================================================================
@dataclasses.dataclass(frozen=True)
class EdgeStack:
    """A batch of timed event graphs as parallel edge arrays.

    Row ``b`` is one candidate graph (a binding / hardware config / static
    order under evaluation).  All rows share the padded edge count ``E`` and
    actor count ``n_actors``; padding slots carry ``weights = -inf``, which
    is the (max,+) neutral element, so they never influence any longest
    path.  Markings may differ per row (buffer sizes are a design axis).
    """

    n_actors: int
    src: np.ndarray       # (B, E) int64
    dst: np.ndarray       # (B, E) int64
    tokens: np.ndarray    # (B, E) int64
    weights: np.ndarray   # (B, E) float64; -inf marks an inactive slot

    @property
    def n_graphs(self) -> int:
        return int(self.weights.shape[0])

    @property
    def n_edges(self) -> int:
        return int(self.weights.shape[1])


def stack_graphs(graphs: Sequence[SDFG]) -> EdgeStack:
    """Pack per-graph edge arrays into one padded :class:`EdgeStack`.

    Graphs may have different topologies and actor counts; rows are padded
    to the maximum edge count with -inf-weight slots and to the maximum
    actor count (extra actors are isolated, so they cannot join a cycle).
    """
    assert graphs, "need at least one graph"
    b = len(graphs)
    n = max(g.n_actors for g in graphs)
    e = max(g.n_channels for g in graphs)
    src = np.zeros((b, e), dtype=np.int64)
    dst = np.zeros((b, e), dtype=np.int64)
    tokens = np.ones((b, e), dtype=np.int64)
    weights = np.full((b, e), NEG_INF)
    for i, g in enumerate(graphs):
        s, d, w, m = g.edges_arrays()
        k = s.size
        src[i, :k] = s
        dst[i, :k] = d
        weights[i, :k] = w
        tokens[i, :k] = m
    return EdgeStack(n_actors=n, src=src, dst=dst, tokens=tokens, weights=weights)


def _bisection_bounds(
    stack: EdgeStack, upper: np.ndarray, lo0: Optional[np.ndarray]
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Shared lambda-search bootstrap for both mcr backends.

    Returns ``(lo, hi, has_cycle)``: the per-row lower bound from one-token
    self-loop cycles folded with the caller's sound ``lo0`` bounds, the
    bisection interval top ``max(upper, lo) + 1``, and which rows are
    already known to contain a cycle.
    """
    finite = np.isfinite(stack.weights)
    self_loop = finite & (stack.src == stack.dst) & (stack.tokens > 0)
    ratio = np.where(self_loop, stack.weights / np.maximum(stack.tokens, 1), NEG_INF)
    lo = np.maximum(ratio.max(axis=1, initial=NEG_INF), 0.0)
    has_cycle = ratio.max(axis=1, initial=NEG_INF) > NEG_INF
    if lo0 is not None:
        lo0 = np.asarray(lo0, dtype=np.float64)
        lo = np.maximum(lo, np.where(np.isfinite(lo0), lo0, NEG_INF))
        has_cycle |= np.isfinite(lo0)
    hi = np.maximum(upper, lo) + 1.0
    return lo, hi, has_cycle


def _upper_path_bound(
    stack: EdgeStack,
    order: np.ndarray,
    uniq_keys: np.ndarray,
    seg_starts: np.ndarray,
) -> np.ndarray:
    """(B,) sound upper bound on any simple-path (hence cycle) weight.

    A simple path or cycle enters each node at most once, so its weight is
    bounded by the per-row sum over nodes of the (positive part of the)
    heaviest incoming edge.  Much tighter than summing every positive edge
    weight when the average in-degree is high, which shrinks both the
    bisection interval and the distance threshold that detects a pumping
    positive cycle.
    """
    b, n = stack.n_graphs, stack.n_actors
    max_in = np.full(b * n, NEG_INF)
    max_in[uniq_keys] = np.maximum.reduceat(stack.weights.ravel()[order], seg_starts)
    return np.clip(max_in.reshape(b, n), 0.0, None).sum(axis=1)


def _positive_cycle_masks(
    stack: EdgeStack,
    lam: np.ndarray,
    src_ord: np.ndarray,
    w_ord: np.ndarray,
    t_ord: np.ndarray,
    row_ord: np.ndarray,
    key_row: np.ndarray,
    uniq_keys: np.ndarray,
    seg_starts: np.ndarray,
    upper: np.ndarray,
    active: Optional[np.ndarray] = None,
    *,
    atol: float = 1e-12,
) -> np.ndarray:
    """Per-row: does weights - lam*tokens contain a positive cycle?

    One vectorized longest-path Bellman-Ford over the whole batch.  A row
    resolves early when a relaxation round changes nothing (no positive
    cycle) or when any distance exceeds the row's maximum simple-path
    weight (positive cycle — only a cycle can pump past it).  Rows outside
    ``active`` start resolved: their probe point sits at (or below) the
    true cycle ratio, where relaxation may never settle, and their answer
    is discarded by the caller anyway — without this, one slow row would
    drag every later bisection step to the full n+1 rounds.

    The relaxation runs in destination-key space: only actors with an
    incoming edge (``uniq_keys``) can ever move off the zero start
    distance, and a zero distance can never exceed ``upper + 1``
    (``upper >= 0``), so tracking the ``(n_keys,)`` vector is exact while
    skipping every full ``(b*n,)`` copy/compare of the dense form.  Edge
    arrays arrive pre-permuted into segment order (``*_ord``), removing
    the per-round gather through ``order``.
    """
    b, n = stack.n_graphs, stack.n_actors
    ww = w_ord - lam[row_ord] * t_ord
    dist = np.zeros(b * n)
    dist_k = np.zeros(len(uniq_keys))
    over_key = upper[key_row] + 1.0
    positive = np.zeros(b, dtype=bool)
    resolved = np.zeros(b, dtype=bool) if active is None else ~active
    for _ in range(n + 1):
        seg_max = np.maximum.reduceat(dist[src_ord] + ww, seg_starts)
        improved = (seg_max - dist_k) > atol
        row_changed = np.bincount(key_row, weights=improved, minlength=b) > 0
        resolved |= ~row_changed
        np.maximum(dist_k, seg_max, out=dist_k)
        over = (
            np.bincount(key_row, weights=dist_k > over_key, minlength=b) > 0
        ) & ~resolved
        positive |= over
        resolved |= over
        dist[uniq_keys] = dist_k
        if resolved.all():
            break
    # rows still improving after n+1 rounds must contain a positive cycle
    positive |= ~resolved
    return positive


def mcr_batch(
    stack: EdgeStack,
    *,
    rel_tol: float = 1e-8,
    max_steps: int = 80,
    backend: str = "auto",
    lo0: Optional[np.ndarray] = None,
    detect_deadlock: bool = False,
    devices: Optional[Sequence] = None,
) -> np.ndarray:
    """Maximum cycle ratio for every row of an :class:`EdgeStack`.

    Lambda-search: a positive cycle in ``weights - lam*tokens`` exists iff
    ``lam < rho_max`` — all rows bisect together.  Inputs must be live
    graphs (a zero-token cycle drives the result to the upper bound instead
    of ``inf``); every graph built by this pipeline is live by construction.
    ``detect_deadlock=True`` adds one probe at the interval top, where any
    remaining positive cycle must be a zero-token one (every token-carrying
    cycle's ratio is bounded by ``upper < hi``), and reports those rows as
    ``inf`` — for callers feeding graphs of unknown liveness.

    Returns a ``(B,)`` float64 array of cycle ratios in the same time unit
    as ``stack.weights`` (microseconds throughout this pipeline);
    ``-inf`` marks an acyclic row.  ``lo0``, when given, is a ``(B,)``
    per-row *sound lower bound* on the cycle ratio (the ratio of any cycle
    the caller knows exists — e.g. a TDMA order cycle's compute sum); it
    shrinks the bisection interval and never changes the result.

    ``backend``: ``"edges"`` (numpy float64, exact — the bit-exactness
    oracle and the default on hosts without an accelerator), ``"csr-jit"``
    (the same exact float64 search as one jitted device program with
    multi-lambda probing — default when any non-CPU device is present),
    ``"dense"`` (Pallas/jnp max-plus matrix squaring, float32, opt-in), or
    ``"auto"``.

    ``devices`` (``"csr-jit"`` only): two or more jax devices shard the
    batch axis — contiguous row chunks solved concurrently, one per
    device, bit-identical to the unsharded solve; a single device pins
    the solve to it.  Forces ``"csr-jit"`` under ``"auto"``.
    """
    if backend == "auto":
        backend = (
            "csr-jit" if (_on_accelerator() or (devices and len(devices) > 1))
            else "edges"
        )
    if devices and backend != "csr-jit":
        raise ValueError(
            f"devices= requires the 'csr-jit' backend, got {backend!r}"
        )
    if backend == "dense":
        if detect_deadlock:
            raise ValueError("detect_deadlock is not supported by 'dense'")
        # float32 squaring can't resolve below ~1e-4 relative; honor a
        # caller-requested looser tolerance but clamp tighter requests
        return _mcr_batch_dense(
            stack, max_steps=max_steps, rel_tol=max(rel_tol, 1e-4), lo0=lo0
        )
    if backend == "csr-jit":
        return _mcr_batch_csr(
            stack, max_steps=max_steps, rel_tol=rel_tol, lo0=lo0,
            detect_deadlock=detect_deadlock, devices=devices,
        )
    assert backend == "edges", backend

    b, n, e = stack.n_graphs, stack.n_actors, stack.n_edges
    if e == 0:
        return np.full(b, NEG_INF)

    # flat batched CSR over (row, dst): segment-max targets, computed once
    rows = np.arange(b, dtype=np.int64)[:, None]
    flat_src = (rows * n + stack.src).ravel()
    flat_dst = (rows * n + stack.dst).ravel()
    order = np.argsort(flat_dst, kind="stable")
    uniq_keys, seg_starts = np.unique(flat_dst[order], return_index=True)
    # segment-ordered edge views + key->row map, hoisted out of the probes
    src_ord = flat_src[order]
    w_ord = stack.weights.ravel()[order]
    t_ord = stack.tokens.ravel()[order]
    row_ord = order // e
    key_row = uniq_keys // n

    upper = _upper_path_bound(stack, order, uniq_keys, seg_starts)
    lo, hi, has_cycle = _bisection_bounds(stack, upper, lo0)

    deadlocked = np.zeros(b, dtype=bool)
    if detect_deadlock:
        deadlocked = _positive_cycle_masks(
            stack, hi, src_ord, w_ord, t_ord, row_ord, key_row,
            uniq_keys, seg_starts, upper, None,
        )

    for _ in range(max_steps):
        tol = rel_tol * np.maximum(1.0, np.abs(hi))
        active = ((hi - lo) > tol) & ~deadlocked
        if not active.any():
            break
        mid = np.where(active, 0.5 * (lo + hi), lo)
        pos = _positive_cycle_masks(
            stack, mid, src_ord, w_ord, t_ord, row_ord, key_row,
            uniq_keys, seg_starts, upper, active,
        )
        has_cycle |= active & pos
        lo = np.where(active & pos, mid, lo)
        hi = np.where(active & ~pos, mid, hi)
    # rows that never showed a positive cycle at any probed lambda (and have
    # no self-loop cycle) are acyclic: no cycle bounds their throughput
    res = np.where(has_cycle, 0.5 * (lo + hi), NEG_INF)
    return np.where(deadlocked, np.inf, res) if detect_deadlock else res


@obs.span("pack")
def _pack_csr_chunk(
    stack: EdgeStack, lo0: Optional[np.ndarray]
) -> Optional[tuple]:
    """Host-side packing of one (chunk of an) EdgeStack for the device
    bisection: flat batched CSR -> ELL operands + bisection bounds.

    Returns ``(operands, lo, hi, has_cycle)`` or ``None`` when the
    chunk has no finite edge at all (every row is acyclic padding — the
    caller reports those rows as ``-inf`` without a solve).  Packing a
    row subset independently is exact: the ELL width tracks the chunk's
    own in-degree profile and pad slots carry the ``-inf`` neutral
    element, so per-row results never depend on which rows share the
    pack.  One of three layouts, chosen from the stack alone
    (:func:`_choose_layout`):

    * ``"shared"``: every row with a finite edge has the same topology
      (the subset candidates of one app); one ELL over it with the rows
      as replicas (:func:`_pack_shared`);
    * ``"split"``: the rows share a base of columns and differ in the
      rest (the binding candidates of one app: one self, flow and buffer
      base, per-row order edges); the base packs as ``"shared"`` does and
      the rest as a per-replica ELL keyed by the same nodes
      (:func:`_pack_shared` too);
    * ``"per_row"``: any other stack, one ELL over every row's nodes
      (:func:`_pack_per_row`).

    Every layout gives each node's edges in the same slot order, so the
    solve's results and counts are the same in any of them.
    """
    layout, ref, same = _choose_layout(stack)
    if layout == "per_row":
        return _pack_per_row(stack, lo0)
    return _pack_shared(stack, lo0, ref, same)


def _choose_layout(
    stack: EdgeStack,
) -> tuple[str, Optional[int], Optional[np.ndarray]]:
    """``(layout, ref, same)``: the ELL layout of :func:`_pack_csr_chunk`,
    the first row with a finite edge, and the ``(E,)`` columns that pack
    as the shared base.  Rows with no finite edge (all--inf padding) do
    not count, and a stack of one row packs per row.

    A column is shared when every counted row has row ``ref``'s finite
    mask, ``src``, ``dst`` and ``tokens`` on it and, at its node, it
    comes before every column that is not: each node's slots then keep
    the column order of the per-row pack.  All columns shared:
    ``"shared"``.  Else ``"split"`` when some finite column is shared
    and the split gathers fewer slices a round than the per-row pack,
    ``n*d0 + n*d1*B < B*n*d``; else ``"per_row"``.
    """
    fin = np.isfinite(stack.weights)
    real = fin.any(axis=1)
    if stack.n_graphs < 2 or not real.any():
        return "per_row", None, None
    ref = int(np.argmax(real))
    same = fin[real] == fin[ref]
    for a in (stack.src, stack.dst, stack.tokens):
        same &= (a[real] == a[ref]) | ~fin[ref]
    same = same.all(axis=0)
    if same.all():
        return "shared", ref, same
    b, (n, e) = stack.n_graphs, (stack.n_actors, stack.n_edges)
    # a common column after a node's first column of its own (a shortcut
    # that every row happens to share) goes with the per-row edges
    rows, cols = np.nonzero(fin & ~same)
    first = np.full(n, e)
    np.minimum.at(first, stack.dst[rows, cols], cols)
    same &= ~fin[ref] | (np.arange(e) < first[stack.dst[ref]])
    base = same & fin[ref]
    if not base.any():
        return "per_row", ref, same
    rows, cols = np.nonzero(fin & ~same)
    d0 = _pow2(np.bincount(stack.dst[ref, base]).max())
    d1 = _bucket_size(int(np.bincount(rows * n + stack.dst[rows, cols]).max()))
    rows, cols = np.nonzero(fin)
    d = _pow2(np.bincount(rows * n + stack.dst[rows, cols]).max())
    if n * d0 + n * d1 * b < b * n * d:
        return "split", ref, same
    return "per_row", ref, same


def _pack_shared(
    stack: EdgeStack, lo0: Optional[np.ndarray], ref: int, same: np.ndarray
) -> tuple:
    """:func:`_pack_csr_chunk` for rows that share row ``ref``'s finite
    ``same`` columns: one ELL over ``n`` nodes with local ids,
    ``ell_w`` ``(n, d0, B)``.  The rows' other finite columns, when there
    are any (the split layout), pack per replica at the same nodes in
    column order: sources, weights and tokens each ``(n, d1, B)``, ``d1``
    on a shape bucket (:func:`_bucket_size`)."""
    b, n = stack.n_graphs, stack.n_actors
    fin = np.isfinite(stack.weights)
    keep = same & fin[ref]
    dst = stack.dst[ref, keep]
    order = np.argsort(dst, kind="stable")
    uniq_keys, seg_starts = np.unique(dst[order], return_index=True)
    w_ord = stack.weights[:, keep][:, order]          # (B, E'); pads -inf
    max_in = np.full((b, n), NEG_INF)
    max_in[:, uniq_keys] = np.maximum.reduceat(w_ord, seg_starts, axis=1)
    operands = _ell_pack(
        stack.src[ref, keep][order], dst[order], w_ord.T,
        stack.tokens[ref, keep][order].astype(np.float64), n,
        uniq_keys, seg_starts,
    )
    rows, cols = np.nonzero(fin & ~same)
    if rows.size:
        # keyed node-major, replica minor; the stable sort keeps each
        # (node, replica)'s edges in column order
        key = stack.dst[rows, cols] * b + rows
        order = np.argsort(key, kind="stable")
        rows, cols, key = rows[order], cols[order], key[order]
        uniq_keys, seg_starts = np.unique(key, return_index=True)
        w_ord = stack.weights[rows, cols]
        max_rep = np.full(n * b, NEG_INF)
        max_rep[uniq_keys] = np.maximum.reduceat(w_ord, seg_starts)
        max_in = np.maximum(max_in, max_rep.reshape(n, b).T)
        counts = np.diff(np.append(seg_starts, key.size))
        d1 = _bucket_size(int(counts.max()))
        pos = np.arange(key.size) - np.repeat(seg_starts, counts)
        rep_src = np.zeros((n * b, d1), dtype=np.int32)
        rep_w = np.full((n * b, d1), NEG_INF)
        rep_t = np.zeros((n * b, d1))
        rep_src[key, pos] = stack.src[rows, cols]
        rep_w[key, pos] = w_ord
        rep_t[key, pos] = stack.tokens[rows, cols]
        operands += tuple(np.ascontiguousarray(a.reshape(n, b, d1)
                                               .transpose(0, 2, 1))
                          for a in (rep_src, rep_w, rep_t))
    # the per-row simple-path bound: the same maxima as _pack_per_row's,
    # summed over the same (B, n) array
    upper = np.clip(max_in, 0.0, None).sum(axis=1)
    lo, hi, has_cycle = _bisection_bounds(stack, upper, lo0)
    return operands, lo, hi, has_cycle


def _pack_per_row(
    stack: EdgeStack, lo0: Optional[np.ndarray]
) -> Optional[tuple]:
    """:func:`_pack_csr_chunk` row by row: one ELL over ``B*n`` nodes,
    ``ell_w`` ``(B*n, d, 1)``."""
    b, n = stack.n_graphs, stack.n_actors
    rows = np.arange(b, dtype=np.int64)[:, None]
    flat_src = (rows * n + stack.src).ravel()
    flat_dst = (rows * n + stack.dst).ravel()
    # drop -inf padding slots before building the device layout: they all
    # target actor 0 of their row (EdgeStack zero-fills indices), so keeping
    # them would blow the ELL width up to the padding count; the neutral
    # element contributes nothing anyway
    keep = np.isfinite(stack.weights.ravel())
    flat_src = flat_src[keep]
    flat_dst = flat_dst[keep]
    w_flat = stack.weights.ravel()[keep]
    t_flat = stack.tokens.ravel()[keep].astype(np.float64)
    if flat_dst.size == 0:
        return None
    order = np.argsort(flat_dst, kind="stable")
    uniq_keys, seg_starts = np.unique(flat_dst[order], return_index=True)
    src_ord = flat_src[order]
    dst_ord = flat_dst[order]
    w_ord = w_flat[order]
    t_ord = t_flat[order]

    # per-row simple-path bound (same construction as _upper_path_bound,
    # over the filtered edge set — identical values, pads are -inf)
    max_in = np.full(b * n, NEG_INF)
    max_in[uniq_keys] = np.maximum.reduceat(w_ord, seg_starts)
    upper = np.clip(max_in.reshape(b, n), 0.0, None).sum(axis=1)
    lo, hi, has_cycle = _bisection_bounds(stack, upper, lo0)

    operands = _ell_pack(
        src_ord, dst_ord, w_ord[:, None], t_ord, b * n, uniq_keys, seg_starts
    )
    return operands, lo, hi, has_cycle


@obs.span("solve")
def _mcr_batch_csr(
    stack: EdgeStack,
    *,
    max_steps: int = 80,
    rel_tol: float = 1e-8,
    lo0: Optional[np.ndarray] = None,
    detect_deadlock: bool = False,
    k_probes: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> np.ndarray:
    """Device-resident exact lambda-search (the ``"csr-jit"`` backend).

    Same flat batched CSR packing and path bounds as the ``"edges"`` path,
    but the entire bisection — multi-lambda probes, Bellman-Ford
    relaxation rounds, interval updates — runs inside one jitted float64
    program (:func:`repro.kernels.maxplus_bellman.csr_bisect`): zero
    host/device round-trips per probe, and every relaxation sweep shrinks
    the interval ``(K+1)x``.  Exact to the same ``rel_tol`` contract as
    ``"edges"``; the two agree to bisection-interval width on every row.

    ``devices`` (two or more jax devices) shards the batch axis: the rows
    split into ``len(devices)`` contiguous chunks, each packed and solved
    on its own device with all chunks in flight at once
    (:func:`repro.kernels.maxplus_bellman.mcr_bisect_device_sharded`).
    Per-row results are bit-identical to the unsharded solve — the
    lambda-search is row-local — so device count never changes a result.
    A single device in ``devices`` pins the unsharded solve to it.
    """
    from repro.kernels import maxplus_bellman as kbell

    b, n, e = stack.n_graphs, stack.n_actors, stack.n_edges
    if e == 0:
        return np.full(b, NEG_INF)
    if k_probes is None:
        k_probes = kbell.DEFAULT_K_PROBES
    # multi-probe steps shrink the interval (k+1)x per sweep, so the
    # classic bisection budget over-covers by the same log factor
    steps = max(4, int(math.ceil(max_steps / math.log2(k_probes + 1))) + 1)

    devices = list(devices) if devices else []
    n_chunks = min(len(devices), b) if len(devices) > 1 else 1

    if n_chunks <= 1:
        packed = _pack_csr_chunk(stack, lo0)
        if packed is None:
            return np.full(b, NEG_INF)
        operands, lo, hi, has_cycle = packed
        lo, hi, has_cycle, deadlocked = kbell.mcr_bisect_device(
            operands, lo, hi, has_cycle,
            n_actors=n, rel_tol=rel_tol, k_probes=k_probes, max_steps=steps,
            detect_deadlock=detect_deadlock,
            device=devices[0] if devices else None,
        )
        res = np.where(has_cycle, 0.5 * (lo + hi), NEG_INF)
        return np.where(deadlocked, np.inf, res) if detect_deadlock else res

    # sharded: contiguous near-equal row chunks, chunk k on devices[k]
    # (the launch-layer sharding rule, so boundaries match everywhere).
    # Every chunk is padded with all--inf rows to the LARGEST chunk's row
    # count: with a bucket-padded caller batch the per-device solve shape
    # is then identical across chunks AND across calls, so each device
    # compiles once and stays on its cached executable.  Pad rows carry
    # no finite edge — they start converged and never touch real rows.
    from repro.launch.sharding import row_chunks

    res = np.full(b, NEG_INF)
    dead = np.zeros(b, dtype=bool)
    chunk_slices = row_chunks(b, n_chunks)
    rows_max = max(sl.stop - sl.start for sl in chunk_slices)
    chunks, slices, devs = [], [], []
    for k, sl in enumerate(chunk_slices):
        m = sl.stop - sl.start
        pad = rows_max - m
        src, dst = stack.src[sl], stack.dst[sl]
        tok, wts = stack.tokens[sl], stack.weights[sl]
        lo0_c = lo0[sl] if lo0 is not None else None
        if pad:
            src = np.concatenate([src, np.zeros((pad, e), dtype=src.dtype)])
            dst = np.concatenate([dst, np.zeros((pad, e), dtype=dst.dtype)])
            tok = np.concatenate([tok, np.ones((pad, e), dtype=tok.dtype)])
            wts = np.concatenate([wts, np.full((pad, e), NEG_INF)])
            if lo0_c is not None:
                lo0_c = np.concatenate([lo0_c, np.full(pad, NEG_INF)])
        sub = EdgeStack(n_actors=n, src=src, dst=dst, tokens=tok, weights=wts)
        packed = _pack_csr_chunk(sub, lo0_c)
        if packed is None:
            continue                       # all-padding rows stay -inf
        chunks.append(packed)
        slices.append(sl)
        devs.append(devices[k % len(devices)])
    if not chunks:
        return res
    lo, hi, has_cycle, deadlocked = kbell.mcr_bisect_device_sharded(
        chunks, devs,
        n_actors=n, rel_tol=rel_tol, k_probes=k_probes, max_steps=steps,
        detect_deadlock=detect_deadlock,
    )
    for k, sl in enumerate(slices):
        m = sl.stop - sl.start
        part = slice(k * rows_max, k * rows_max + m)
        res[sl] = np.where(
            has_cycle[part], 0.5 * (lo[part] + hi[part]), NEG_INF
        )
        dead[sl] = deadlocked[part]
    return np.where(dead, np.inf, res) if detect_deadlock else res


def _pow2(x: int) -> int:
    """The next power of two ``>= x`` (``>= 1``): the ELL width bucket."""
    return 1 << (max(int(x), 1) - 1).bit_length()


def _bucket_size(x: int) -> int:
    """Round up to the next pow2-ish bucket (1, 2, 3, 4, 6, 8, 12, 16, …).

    Half-steps between powers of two keep the bucket within 2x of the
    request (< 50% padding waste, vs the plain next-power-of-two's ~100%)
    while collapsing the long tail of one-off shapes onto a few buckets.
    """
    if x <= 1:
        return 1
    p = 1 << (x - 1).bit_length()          # next power of two >= x
    if x <= (3 * p) // 4:
        return (3 * p) // 4
    return p


def _ell_pack(
    src_ord: np.ndarray,
    dst_ord: np.ndarray,
    w_ord: np.ndarray,
    t_ord: np.ndarray,
    n_keys: int,
    uniq_keys: np.ndarray,
    seg_starts: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """dst-sorted flat edges -> ELLPACK ``(n_keys, d_max)`` incoming-edge
    rows; ``w_ord`` is ``(E, R)``, one weight per replica, and ``ell_w``
    ``(n_keys, d_max, R)``.

    Pad slots point at node 0 with -inf weight (the (max,+) neutral), so
    the degree-axis max ignores them.  ``d_max`` is rounded up to the next
    power of two: the device program's shapes then only change when the
    in-degree profile crosses a bucket, not on every edge-count wiggle.
    """
    counts = np.diff(np.append(seg_starts, src_ord.size))
    d_max = _pow2(int(counts.max(initial=1)))
    pos = np.arange(src_ord.size) - np.repeat(seg_starts, counts)
    row_idx = dst_ord
    ell_src = np.zeros((n_keys, d_max), dtype=np.int32)
    ell_w = np.full((n_keys, d_max, w_ord.shape[1]), NEG_INF)
    ell_t = np.zeros((n_keys, d_max))
    ell_src[row_idx, pos] = src_ord
    ell_w[row_idx, pos] = w_ord
    ell_t[row_idx, pos] = t_ord
    return ell_src, ell_w, ell_t


def _on_accelerator() -> bool:
    # lazy: keep repro.core importable without pulling jax in at load time;
    # any non-CPU jax device (TPU *or* GPU)
    from repro.kernels.ops import _on_accelerator as kernels_on_accel

    return kernels_on_accel()


#: squaring rounds the last :func:`_mcr_batch_dense` call actually ran,
#: one entry per bisection step (instrumentation for tests/benchmarks).
#: With PR-3 path-doubling shortcut edges in the stack
#: (:func:`~repro.core.engine.stack_hardware_aware` with
#: ``relax_shortcuts=True``) the value fixpoint arrives after about
#: log2(shortcut-reduced hop diameter) rounds — the log2(n) bound is
#: only the sound worst-case cap.
_DENSE_LAST_ROUNDS: list[int] = []


def _maxplus_fixpoint(a: np.ndarray, b: np.ndarray) -> bool:
    """True when one more max-plus squaring left the closure unchanged.

    Supports must match exactly; finite entries may drift by float32
    re-association slack (the max of the SAME path weights summed in a
    different association order), so they compare under a relative
    tolerance two decades tighter than the dense backend's 1e-4 growth
    threshold.  A positive cycle above that threshold keeps pumping the
    on-cycle entries geometrically (budget doubles each squaring), so it
    can never masquerade as a fixpoint.
    """
    fa, fb = np.isfinite(a), np.isfinite(b)
    if not np.array_equal(fa, fb):
        return False
    av, bv = a[fa], b[fa]
    if av.size == 0:
        return True
    return bool(
        (np.abs(av - bv) <= 1e-6 * np.maximum(1.0, np.abs(bv))).all()
    )


def _mcr_batch_dense(
    stack: EdgeStack,
    *,
    max_steps: int = 60,
    rel_tol: float = 1e-4,
    lo0: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Dense-kernel lambda-search: positive-cycle detection by max-plus
    matrix squaring through :func:`repro.kernels.ops.maxplus_bmm`.

    ``W[b, i, j] = max over edges j->i of (w - lam*m)`` with a 0 diagonal
    (the (max,+) identity is folded in), so ``W^(2^k)`` holds longest paths
    of length <= 2^k.  With ``2^k >= n_actors`` the paths saturate unless a
    positive cycle keeps pumping — one extra relaxation detects growth.
    float32 on the kernel path, so tolerances are looser than ``"edges"``.

    The squaring count is NOT fixed at log2(n): that is only the cap.
    Each bisection step squares until the closure stops changing
    (:func:`_maxplus_fixpoint`), which it does once ``2^k`` covers the
    graph's hop diameter.  Stacks built by
    :func:`~repro.core.engine.stack_hardware_aware` with
    ``relax_shortcuts=True`` carry the PR-3 order-cycle path-doubling
    shortcut edges, which collapse the length-k TDMA order cycles — the
    hop diameter of the hardware-aware graph — to O(log k) hops, so the
    fixpoint lands after ~log2(shortcut-reduced diameter) rounds instead
    of log2(n).  Saturation implies no positive cycle above the growth
    threshold (a positive cycle doubles its pumping budget every
    squaring, growing geometrically), so the early exit never flips the
    per-step verdict.  Realized round counts land in
    :data:`_DENSE_LAST_ROUNDS` for tests and benchmarks.
    """
    from repro.kernels import ops as kops

    b, n = stack.n_graphs, stack.n_actors
    finite = np.isfinite(stack.weights)
    # loose positive-weight-sum upper bound: the float32 squaring path
    # saturates long before a per-node bound would pay off
    wpos = np.where(finite & (stack.weights > 0), stack.weights, 0.0)
    upper = wpos.sum(axis=1)
    lo, hi, has_cycle = _bisection_bounds(stack, upper, lo0)

    rows = np.arange(b, dtype=np.int64)[:, None]
    flat = (rows * n * n + stack.dst * n + stack.src).ravel()
    order = np.argsort(flat, kind="stable")
    uniq_keys, seg_starts = np.unique(flat[order], return_index=True)
    diag = np.arange(n)
    n_sq_cap = max(1, int(math.ceil(math.log2(max(n, 2)))))
    _DENSE_LAST_ROUNDS.clear()

    for _ in range(max_steps):
        tol = rel_tol * np.maximum(1.0, np.abs(hi))
        active = (hi - lo) > tol
        if not active.any():
            break
        mid = np.where(active, 0.5 * (lo + hi), lo)
        ww = (stack.weights - mid[:, None] * stack.tokens).ravel()
        w_dense = np.full(b * n * n, NEG_INF, dtype=np.float32)
        w_dense[uniq_keys] = np.maximum.reduceat(
            ww[order].astype(np.float32), seg_starts
        )
        w_dense = w_dense.reshape(b, n, n)
        w_dense[:, diag, diag] = np.maximum(w_dense[:, diag, diag], 0.0)

        m_pow = w_dense
        rounds = 0
        for _ in range(n_sq_cap):
            m_new = np.asarray(kops.maxplus_bmm(m_pow, m_pow))
            rounds += 1
            saturated = _maxplus_fixpoint(m_new, m_pow)
            m_pow = m_new
            if saturated:
                break
        _DENSE_LAST_ROUNDS.append(rounds)
        dist = m_pow.max(axis=2)                       # paths from 0-vector
        dist1 = (w_dense + dist[:, None, :]).max(axis=2)
        growth = np.maximum(1.0, np.abs(dist)) * 1e-4
        pos = np.logical_or.reduce(dist1 > dist + growth, axis=1)
        has_cycle |= active & pos
        lo = np.where(active & pos, mid, lo)
        hi = np.where(active & ~pos, mid, hi)
    # rows that never showed a positive cycle at any probed lambda (and have
    # no self-loop cycle) are acyclic — same convention as the edges backend
    return np.where(has_cycle, 0.5 * (lo + hi), NEG_INF).astype(np.float64)


def _dense_weight_matrix(
    stack: EdgeStack, mask: np.ndarray, *, dtype=np.float32
) -> np.ndarray:
    """(B, n, n) dense ``W[b, d, s] = max weight over masked edges s->d``."""
    b, n = stack.n_graphs, stack.n_actors
    w = np.full(b * n * n, NEG_INF, dtype=dtype)
    rows = np.arange(b, dtype=np.int64)[:, None]
    flat = (rows * n * n + stack.dst * n + stack.src).ravel()
    sel = mask.ravel()
    fl = flat[sel]
    if fl.size:
        ww = stack.weights.ravel()[sel].astype(dtype)
        order = np.argsort(fl, kind="stable")
        uniq, seg = np.unique(fl[order], return_index=True)
        w[uniq] = np.maximum.reduceat(ww[order], seg)
    return w.reshape(b, n, n)


def maxplus_matrix_batch(stack: EdgeStack) -> np.ndarray:
    """Batched Eq.-4 matrices: ``T[b] = A0*[b] (x) A1[b]`` as (B, n, n).

    The per-graph construction (:func:`maxplus_matrix`) walks the 0-token
    subgraph in topological order; the batched one instead computes the
    Kleene star ``A0* = (I (+) A0)^(2^ceil(log2 n))`` by repeated max-plus
    squaring through the Pallas ``maxplus_bmm`` kernel — every candidate's
    closure advances together.  Multi-token edges are conservatively kept
    as one-token dependencies (same convention as :func:`maxplus_matrix`);
    exact multi-token periods come from :func:`mcr_batch`.  Rows must be
    live (an acyclic 0-token subgraph), which this pipeline guarantees.
    """
    from repro.kernels import ops as kops

    n = stack.n_actors
    finite = np.isfinite(stack.weights)
    w0 = _dense_weight_matrix(stack, finite & (stack.tokens == 0))
    w1 = _dense_weight_matrix(stack, finite & (stack.tokens >= 1))
    diag = np.arange(n)
    star = w0
    star[:, diag, diag] = np.maximum(star[:, diag, diag], 0.0)
    for _ in range(max(1, int(math.ceil(math.log2(max(n, 2)))))):
        star = np.asarray(kops.maxplus_bmm(star, star))
    return np.asarray(kops.maxplus_bmm(star, w1))


def evolve_batch(
    t_batch: np.ndarray, *, iters: int = 64, x0: Optional[np.ndarray] = None
) -> tuple[np.ndarray, np.ndarray]:
    """Iterate ``x(k) = T (x) x(k-1)`` for a whole batch of candidates.

    Returns ``(x, period_estimate)``: the final (renormalized) start-time
    vectors, whose *relative* offsets converge to the steady-state static
    schedule, and the mean per-iteration growth over the tail half of the
    run — a float32 MCM estimate (use :func:`mcr_batch` when the exact
    period is needed).  Each step renormalizes by the row maximum (max-plus
    scaling invariance) so float32 never accumulates drift.
    """
    from repro.kernels import ops as kops

    t_batch = np.asarray(t_batch, dtype=np.float32)
    b, n, _ = t_batch.shape
    if x0 is None:
        x = np.zeros((b, n), dtype=np.float32)
    else:
        x = np.array(x0, dtype=np.float32, copy=True)
    warm = max(1, iters // 2)
    growth = np.zeros(b)
    counted = 0
    for k in range(iters):
        x = np.asarray(kops.maxplus_bmv(t_batch, x))
        mx = np.where(np.isfinite(x), x, NEG_INF).max(axis=1)
        step = np.where(np.isfinite(mx), mx, 0.0)
        x = x - step[:, None].astype(np.float32)
        if k >= warm:
            growth += step
            counted += 1
    return x.astype(np.float64), growth / max(counted, 1)


def throughput_batch(
    graphs: Sequence[SDFG],
    *,
    backend: str = "auto",
    rel_tol: float = 1e-8,
    group_factor: float = 1.5,
) -> np.ndarray:
    """Per-graph throughput (1/MCR) for a batch of graphs.

    Rows of an :class:`EdgeStack` all pay the padded maximum edge and actor
    count, so stacking a 20-actor graph with a 700-actor one wastes most of
    the sweep.  Graphs are therefore grouped into similar-size sub-stacks
    (within ``group_factor`` in both actors and edges) and each group is
    analyzed in one :func:`mcr_batch` call; a homogeneous batch (the common
    sweep/admission shape) stays a single call.
    """
    order = sorted(
        range(len(graphs)), key=lambda i: (graphs[i].n_actors, graphs[i].n_channels)
    )
    groups: list[list[int]] = []
    for i in order:
        if groups:
            anchor = graphs[groups[-1][0]]
            g = graphs[i]
            if (
                g.n_actors <= group_factor * max(anchor.n_actors, 1)
                and g.n_channels <= group_factor * max(anchor.n_channels, 1)
            ):
                groups[-1].append(i)
                continue
        groups.append([i])

    out = np.zeros(len(graphs))
    for grp in groups:
        rho = mcr_batch(
            stack_graphs([graphs[i] for i in grp]), backend=backend, rel_tol=rel_tol
        )
        ok = np.isfinite(rho) & (rho > 0)
        out[np.asarray(grp)[ok]] = 1.0 / rho[ok]
    return out


# ======================================================================
def throughput(g: SDFG, *, method: str = "howard") -> float:
    """Application throughput = 1 / MCM (paper's headline metric)."""
    if method == "howard":
        rho = mcr_howard(g)
    elif method == "binary":
        rho = mcr_binary_search(g)
    elif method == "power":
        rho = mcm_power_iteration(maxplus_matrix(g))
    else:
        raise ValueError(f"unknown method {method!r}")
    if rho <= 0 or not np.isfinite(rho):
        return 0.0
    return 1.0 / rho
