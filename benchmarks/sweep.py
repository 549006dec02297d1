"""Design-space sweep benchmark: batched Max-Plus analysis vs the
per-graph Python loop, across the eight Table-1 applications.

  PYTHONPATH=src python -m benchmarks.sweep            # full (all 8 apps)
  PYTHONPATH=src python -m benchmarks.sweep --quick    # 3 small apps

Two sections:

  1. *Fidelity* — full factorial sweep (apps x tile counts x binders);
     batched throughputs are checked against per-graph ``mcr_howard`` and
     must agree within 1e-6 relative.
  2. *Speedup* — a >= 32-candidate binding sweep of one app (shared graph
     topology, the admission-scoring shape); wall-clock of one batched
     ``mcr_batch`` call vs looping ``mcr_binary_search`` per graph (the
     same lambda-search algorithm, un-batched).  Target: >= 5x.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import (
    DYNAP_SE,
    APP_NAMES,
    analyze_candidates,
    build_app,
    build_candidates,
    build_static_orders,
    mcr_howard,
    partition_greedy,
    sdfg_from_clusters,
)
from repro.core.binding import bind_ours, bind_pycarl, bind_spinemap
from repro.core.maxplus import mcr_batch, mcr_binary_search, stack_graphs
from repro.core.sdfg import hardware_aware_sdfg

QUICK_APPS = ("ImgSmooth", "MLP-MNIST", "CNN-MNIST")


# ======================================================================
def fidelity_sweep(apps, tile_counts=(4, 9, 16), binders=("ours", "spinemap", "pycarl")):
    """Factorial sweep; batched analysis must match per-graph Howard."""
    metas, graphs, t_build, _ = build_candidates(
        apps, tile_counts=tile_counts, binders=binders
    )
    t0 = time.perf_counter()
    thr_batched = analyze_candidates(graphs, method="batched")
    t_batched = time.perf_counter() - t0

    t0 = time.perf_counter()
    rhos = np.array([mcr_howard(g) for g in graphs])
    t_howard = time.perf_counter() - t0
    thr_howard = np.where(rhos > 0, 1.0 / np.maximum(rhos, 1e-300), 0.0)

    rel_err = np.abs(thr_batched - thr_howard) / np.maximum(np.abs(thr_howard), 1e-300)
    rows = [("app", "crossbar", "tiles", "binder", "thr_batched", "thr_howard",
             "rel_err")]
    for p, tb, th, re_ in zip(metas, thr_batched, thr_howard, rel_err):
        rows.append((p.app, p.crossbar, p.n_tiles, p.binder,
                     f"{tb:.6e}", f"{th:.6e}", f"{re_:.2e}"))
    ok = bool(np.all(rel_err <= 1e-6))
    summary = (
        f"candidates={len(graphs)} build={t_build:.2f}s "
        f"batched={t_batched:.3f}s howard_loop={t_howard:.3f}s "
        f"max_rel_err={rel_err.max():.2e} within_1e-6={ok}"
    )
    return rows, summary, ok


# ======================================================================
def speedup_sweep(app_name: str = "MLP-MNIST", n_candidates: int = 48,
                  n_tiles: int = 16, seed: int = 0):
    """>= 32 candidate bindings of one app, batched vs per-graph loop.

    The candidate set mimics admission scoring: the three binder outputs
    plus random bindings, all over the same application graph (shared
    topology, differing NoC delays and TDMA order edges).
    """
    hw = dataclasses.replace(DYNAP_SE, n_tiles=n_tiles)
    snn = build_app(app_name)
    cl = partition_greedy(snn, hw)
    app = sdfg_from_clusters(cl, hw=hw)

    bindings = [b(cl, hw).binding for b in (bind_ours, bind_spinemap, bind_pycarl)]
    rng = np.random.default_rng(seed)
    while len(bindings) < n_candidates:
        bindings.append(rng.integers(0, n_tiles, size=cl.n_clusters))
    graphs = []
    for binding in bindings:
        orders, _ = build_static_orders(app, binding, hw, iterations=8)
        graphs.append(hardware_aware_sdfg(app, binding, hw, orders))

    stack = stack_graphs(graphs)
    t0 = time.perf_counter()
    rhos_b = mcr_batch(stack, backend="edges")
    t_batched = time.perf_counter() - t0

    t0 = time.perf_counter()
    rhos_loop = np.array([mcr_binary_search(g, tol=1e-6) for g in graphs])
    t_loop = time.perf_counter() - t0

    t0 = time.perf_counter()
    rhos_h = np.array([mcr_howard(g) for g in graphs])
    t_howard = time.perf_counter() - t0

    rel_err = np.abs(rhos_b - rhos_h) / np.abs(rhos_h)
    speedup = t_loop / max(t_batched, 1e-12)
    rows = [
        ("metric", "value"),
        ("app", app_name),
        ("candidates", len(graphs)),
        ("actors", app.n_actors),
        ("edges_padded", stack.n_edges),
        ("t_batched_s", f"{t_batched:.3f}"),
        ("t_pergraph_loop_s", f"{t_loop:.3f}"),
        ("t_howard_loop_s", f"{t_howard:.3f}"),
        ("speedup_vs_loop", f"{speedup:.1f}x"),
        ("max_rel_err_vs_howard", f"{rel_err.max():.2e}"),
    ]
    ok = speedup >= 5.0
    summary = (
        f"{len(graphs)} candidates: batched {t_batched:.3f}s vs per-graph "
        f"loop {t_loop:.3f}s -> {speedup:.1f}x (target >= 5x: "
        f"{'PASS' if ok else 'MISS'}); howard loop {t_howard:.3f}s; "
        f"max rel err vs howard {rel_err.max():.2e}"
    )
    return rows, summary, ok


# ======================================================================
def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="3 small apps + smaller speedup sweep")
    ap.add_argument("--app", default="MLP-MNIST",
                    help="application for the speedup section")
    ap.add_argument("--candidates", type=int, default=48)
    args = ap.parse_args()

    apps = QUICK_APPS if args.quick else APP_NAMES
    print(f"# fidelity_sweep ({len(apps)} apps)")
    rows, summary, ok_fid = fidelity_sweep(apps)
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", summary)

    print("\n# speedup_sweep")
    rows, summary, ok_speed = speedup_sweep(
        args.app, n_candidates=max(32, args.candidates)
    )
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", summary)

    if not (ok_fid and ok_speed):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
