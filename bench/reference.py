"""Plain reference for the benchmark's correctness comparison.

Imports nothing of the program.  Two pieces:

* ``max_cycle_ratio``: Howard's policy iteration for the maximum cycle
  ratio ``max over cycles C of sum(w) / sum(tokens)`` of one graph given
  as edge arrays.  It finds the critical cycle itself and returns its
  ratio, so it has no bisection tolerance.  ``dtype`` sets the precision
  of every weight and every sum; ``float32`` is the control.
* ``app_periods``: the steady-state period of every resident application,
  rebuilt from the seeded networks, the program's decisions (which
  cluster each neuron went to, each cluster's tile, each application's
  single-tile firing order) and the hardware constants of the
  configuration file: cluster firing times, AER channels and their
  rates, NoC delays, output-buffer back-edges and the per-tile TDMA
  firing cycles (Balaji et al. 2020, sections 3 and 4.4).
"""

from __future__ import annotations

import math

import numpy as np


# ----------------------------------------------------------------------
# maximum cycle ratio
# ----------------------------------------------------------------------
def _prune_acyclic(n, src, dst):
    """Mask of edges whose endpoints can both lie on a cycle."""
    keep = np.ones(src.size, dtype=bool)
    while True:
        indeg = np.bincount(dst[keep], minlength=n)
        outdeg = np.bincount(src[keep], minlength=n)
        alive = (indeg > 0) & (outdeg > 0)
        nxt = keep & alive[src] & alive[dst]
        if nxt.sum() == keep.sum():
            return keep
        keep = nxt


def max_cycle_ratio(n, src, dst, tokens, weights, *, dtype=np.float64,
                    max_iter=10_000):
    """Maximum cycle ratio of one graph; ``-inf`` when it has no cycle.

    Edges with a non-finite weight are absent.  Every cycle must carry a
    token (a live graph).  Howard's algorithm: each node keeps one
    outgoing edge (its policy); the policy graph's cycles give ratios and
    node values, and a node switches to an edge that leads to a higher
    ratio, or to the same ratio with a higher value, until none does.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    weights = np.asarray(weights, dtype=np.float64)
    live = np.isfinite(weights)
    src, dst = src[live], dst[live]
    w = weights[live].astype(dtype)
    t = np.asarray(tokens)[live].astype(dtype)
    keep = _prune_acyclic(n, src, dst)
    if not keep.any():
        return float("-inf")
    src, dst, w, t = src[keep], dst[keep], w[keep], t[keep]
    order = np.argsort(src, kind="stable")
    src, dst, w, t = src[order], dst[order], w[order], t[order]
    nodes = np.unique(src)
    starts = np.searchsorted(src, nodes)
    scale = dtype(max(1.0, float(np.abs(w).max())))
    eps = dtype(np.finfo(dtype).eps) * scale * 64
    # initial policy: the heaviest outgoing edge
    pol = np.zeros(n, dtype=np.int64)
    pol[nodes] = starts + np.array(
        [int(np.argmax(w[s:e])) for s, e in
         zip(starts, np.append(starts[1:], src.size))], dtype=np.int64)
    eta = np.zeros(n, dtype=dtype)
    x = np.zeros(n, dtype=dtype)
    node_list = [int(v) for v in nodes]
    for _ in range(max_iter):
        succ = dst[pol]
        # policy evaluation: one cycle per component of the policy graph
        state = {}                      # 0 on the current walk, 1 done
        for v0 in node_list:
            if v0 in state:
                continue
            path = []
            v = v0
            while v not in state:
                state[v] = 0
                path.append(v)
                v = int(succ[v])
            walked = list(path)
            if state[v] == 0:           # closed a new cycle at v
                k = path.index(v)
                cyc = path[k:]
                es = pol[np.asarray(cyc)]
                tok = t[es].sum(dtype=dtype)
                if tok <= 0:
                    raise ValueError("a cycle without tokens: graph not live")
                ratio = dtype(w[es].sum(dtype=dtype) / tok)
                eta[v] = ratio
                x[v] = 0
                for u in reversed(cyc[1:]):
                    e = pol[u]
                    eta[u] = ratio
                    x[u] = w[e] - ratio * t[e] + x[succ[u]]
                path = path[:k]
            for u in reversed(path):
                e = pol[u]
                eta[u] = eta[succ[u]]
                x[u] = w[e] - eta[u] * t[e] + x[succ[u]]
            for u in walked:
                state[u] = 1
        # policy improvement, first on the ratio, then on the value
        eta_d = eta[dst]
        best_eta = np.maximum.reduceat(eta_d, starts)
        better = best_eta > eta[nodes] + eps
        changed = False
        if better.any():
            for i in np.flatnonzero(better):
                s, e = starts[i], (starts[i + 1] if i + 1 < starts.size
                                   else src.size)
                pol[nodes[i]] = s + int(np.argmax(eta_d[s:e]))
            changed = True
        else:
            val = np.where(np.abs(eta_d - eta[src]) <= eps,
                           w - eta[src] * t + x[dst], -np.inf)
            best_val = np.maximum.reduceat(val, starts)
            gain = best_val > x[nodes] + eps * max(1, len(node_list))
            for i in np.flatnonzero(gain):
                s, e = starts[i], (starts[i + 1] if i + 1 < starts.size
                                   else src.size)
                pol[nodes[i]] = s + int(np.argmax(val[s:e]))
                changed = True
        if not changed:
            return float(eta[nodes].max())
    raise RuntimeError("Howard's iteration did not converge")


def stack_ratios(stack: dict, rows, *, dtype=np.float64) -> np.ndarray:
    """Maximum cycle ratio of the chosen rows of a batch of graphs."""
    return np.array([
        max_cycle_ratio(
            stack["n_actors"], stack["src"][r], stack["dst"][r],
            stack["tokens"][r], stack["weights"][r], dtype=dtype,
        )
        for r in rows
    ])


# ----------------------------------------------------------------------
# steady-state periods of resident applications
# ----------------------------------------------------------------------
def app_graph(snn: dict, cluster_of: np.ndarray, hw: dict, *, dtype):
    """Binding-independent parts of one clustered application.

    Returns firing times ``tau`` (n_clusters,), and the AER channels
    ``(src, dst, rate, tokens)`` with one packet per spike of a source
    neuron to each destination cluster it reaches.
    """
    cluster_of = np.asarray(cluster_of, dtype=np.int64)
    n = int(cluster_of.max()) + 1
    spikes = np.asarray(snn["spikes"], dtype=np.float64)
    out_spikes = np.bincount(cluster_of, weights=spikes, minlength=n)
    tau = (hw["t_fire"] + hw["t_spike_encode"] * out_spikes).astype(dtype)
    pre = np.asarray(snn["pre"], dtype=np.int64)
    post = np.asarray(snn["post"], dtype=np.int64)
    c_pre, c_post = cluster_of[pre], cluster_of[post]
    cut = c_pre != c_post
    pairs = np.unique(pre[cut] * n + c_post[cut])
    pre_n, dst_c = pairs // n, pairs % n
    src_c = cluster_of[pre_n]
    chan, inv = np.unique(src_c * n + dst_c, return_inverse=True)
    rate = np.maximum(
        np.bincount(inv, weights=spikes[pre_n], minlength=chan.size), 1e-6
    )
    ch_src, ch_dst = chan // n, chan % n
    rank = np.full(n, np.iinfo(np.int64).max, dtype=np.int64)
    np.minimum.at(rank, cluster_of, np.asarray(snn["layer_of"], np.int64))
    key = rank * (n + 1) + np.arange(n)
    tokens = (key[ch_dst] <= key[ch_src]).astype(np.int64)
    return tau, (ch_src, ch_dst, rate, tokens)


def _hops(a, b, n_cols):
    return np.abs(a % n_cols - b % n_cols) + np.abs(a // n_cols - b // n_cols)


def mesh_cols(n_tiles: int) -> int:
    """Columns of the most-square mesh with ``cols <= rows``."""
    c = max(1, math.isqrt(n_tiles))
    while c > 1 and n_tiles % c:
        c -= 1
    return c


def app_periods(apps: list, hw: dict, *, dtype=np.float64) -> dict:
    """Period of every application in ``apps`` on the shared chip.

    ``apps`` holds one dict per resident, in the order the program
    concatenates them (by name): ``name``, ``snn`` (the network that was
    clustered), ``cluster_of``, ``binding`` (tile of each cluster) and
    ``order`` (single-tile firing order of the clusters).  Applications
    that share a tile fire in one TDMA cycle on it, ordered application by
    application and, within one, by its firing order.  An application's
    period is the largest cycle ratio of any connected part of the chip
    graph that holds one of its clusters.
    """
    cols = mesh_cols(hw["n_tiles"])
    srcs, dsts, toks, ws = [], [], [], []
    offset = 0
    owner, tile_of, pos, taus = [], [], [], []
    for k, a in enumerate(apps):
        tau, (c_src, c_dst, rate, c_tok) = app_graph(
            a["snn"], a["cluster_of"], hw, dtype=dtype)
        n = tau.size
        binding = np.asarray(a["binding"], dtype=np.int64)
        nodes = offset + np.arange(n)
        hops = _hops(binding[c_src], binding[c_dst], cols)
        delay = np.where(
            hops == 0, 0.0,
            hw["t_route"] + rate * (hw["t_spike_encode"] + hw["t_spike_link"])
            + (hops - 1) * hw["t_spike_link"],
        ).astype(dtype)
        buf = np.maximum(
            1, (hw["output_buffer"] // np.maximum(rate, 1.0)).astype(np.int64))
        srcs += [nodes, offset + c_src, offset + c_dst]
        dsts += [nodes, offset + c_dst, offset + c_src]
        toks += [np.ones(n, np.int64), c_tok, buf]
        ws += [tau, tau[c_dst] + delay, tau[c_src]]
        rank = np.full(n, n, dtype=np.int64)
        order = [int(c) for c in a["order"]]
        rank[order] = np.arange(len(order))
        missing = np.flatnonzero(rank == n)
        rank[missing] = len(order) + np.arange(missing.size)
        owner.append(np.full(n, k))
        tile_of.append(binding)
        pos.append(rank)
        taus.append(tau)
        offset += n
    owner = np.concatenate(owner)
    tile_of = np.concatenate(tile_of)
    pos = np.concatenate(pos)
    tau = np.concatenate(taus)
    # per-tile TDMA cycle: by application, then by firing order
    seq = np.lexsort((pos, owner, tile_of))
    for i in range(seq.size):
        a = seq[i]
        last = i + 1 == seq.size or tile_of[seq[i + 1]] != tile_of[a]
        if last:
            j = i
            while j > 0 and tile_of[seq[j - 1]] == tile_of[a]:
                j -= 1
            b, tok = seq[j], 1
        else:
            b, tok = seq[i + 1], 0
        srcs.append(np.array([a]))
        dsts.append(np.array([b]))
        toks.append(np.array([tok]))
        ws.append(np.array([tau[b]], dtype=dtype))
    src = np.concatenate(srcs)
    dst = np.concatenate(dsts)
    tok = np.concatenate(toks)
    w = np.concatenate(ws).astype(dtype)
    # connected parts (weak: every edge of this graph lies on a cycle)
    parent = np.arange(offset)

    def find(v):
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    for a, b in zip(src.tolist(), dst.tolist()):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[rb] = ra
    label = np.array([find(v) for v in range(offset)])
    ratio = {}
    for root in np.unique(label):
        members = np.flatnonzero(label == root)
        local = np.full(offset, -1)
        local[members] = np.arange(members.size)
        sel = label[src] == root
        ratio[int(root)] = max_cycle_ratio(
            members.size, local[src[sel]], local[dst[sel]], tok[sel], w[sel],
            dtype=dtype)
    return {
        a["name"]: max(ratio[int(r)] for r in np.unique(label[owner == k]))
        for k, a in enumerate(apps)
    }
