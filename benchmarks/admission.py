"""Run-time admission benchmark (paper §5, Table 3 — made multi-tenant).

  PYTHONPATH=src python -m benchmarks.admission            # standalone
  PYTHONPATH=src python -m benchmarks.run admission        # via the runner

Two sections, both recorded into ``BENCH_admission.json``:

  1. *Trajectory* — an :class:`AdmissionController` serving app churn on a
     16-tile chip: register apps once (design time), then rounds of
     admit / finish / evict / re-admit.  Reports admissions/sec; the full
     event trajectory goes into the JSON file.
  2. *Speedup* — one admission decision scoring ``>= 16`` candidate
     bindings: the batched engine (one EdgeStack + ``mcr_batch``) vs the
     serial per-candidate heapq ``SelfTimedExecutor`` replay loop the
     engine replaces.  Acceptance target: >= 3x.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import (
    AdmissionController,
    AdmissionError,
    DYNAP_SE,
    SelfTimedExecutor,
    batch_execute,
    bind_ours,
    partition_greedy,
    project_order,
    sdfg_from_clusters,
    single_tile_order,
    small_app,
)

HW16 = dataclasses.replace(DYNAP_SE, n_tiles=16)


# ======================================================================
# section 1: multi-app admission trajectory
# ======================================================================
def trajectory_bench(n_apps: int = 6, rounds: int = 4, seed: int = 0):
    """Churn ``n_apps`` tenants through admit/finish/evict for ``rounds``."""
    rng = np.random.default_rng(seed)
    ctl = AdmissionController(HW16)

    t_design0 = time.perf_counter()
    names = []
    for i in range(n_apps):
        snn = small_app(
            int(rng.integers(140, 260)), int(rng.integers(1500, 3000)),
            seed=100 + i,
        )
        snn.name = f"app{i}"
        ctl.register(snn)
        names.append(snn.name)
    t_design = time.perf_counter() - t_design0

    n_admits = 0
    t_admit = 0.0
    for r in range(rounds):
        for name in names:
            req = int(rng.integers(1, 5))
            t0 = time.perf_counter()
            try:
                ctl.admit(name, n_tiles_request=req)
                n_admits += 1
            except AdmissionError:
                pass
            t_admit += time.perf_counter() - t0
        # churn: finish half, evict a quarter, keep the rest running
        running = list(ctl.running())
        rng.shuffle(running)
        for name in running[: len(running) // 2]:
            ctl.finish(name)
        for name in running[len(running) // 2 : (3 * len(running)) // 4]:
            ctl.evict(name)
    for name in list(ctl.running()):
        ctl.finish(name)

    admissions_per_sec = n_admits / max(t_admit, 1e-12)
    rows = [
        ("metric", "value"),
        ("apps", n_apps),
        ("rounds", rounds),
        ("admissions", n_admits),
        ("rejections", sum(1 for e in ctl.events if e.kind == "reject")),
        ("evictions", sum(1 for e in ctl.events if e.kind == "evict")),
        ("design_time_s", f"{t_design:.3f}"),
        ("admit_time_s", f"{t_admit:.3f}"),
        ("admissions_per_sec", f"{admissions_per_sec:.1f}"),
    ]
    payload = {
        "n_apps": n_apps,
        "rounds": rounds,
        "n_admissions": n_admits,
        "design_time_s": t_design,
        "admit_time_s": t_admit,
        "admissions_per_sec": admissions_per_sec,
        "trajectory": ctl.trajectory(),
    }
    return rows, payload


# ======================================================================
# section 2: batched engine vs serial heapq scoring of one admission
# ======================================================================
def speedup_bench(n_candidates: int = 16, seed: int = 0,
                  sim_iterations: int = 30):
    """Score ``n_candidates`` free-tile bindings: engine vs heapq loop."""
    rng = np.random.default_rng(seed)
    snn = small_app(1500, 40_000, seed=7)
    snn.name = "score-me"
    cl = partition_greedy(snn, HW16)
    app = sdfg_from_clusters(cl, hw=HW16)
    order, _ = single_tile_order(cl, HW16)

    bindings = [bind_ours(cl, HW16).binding]
    while len(bindings) < n_candidates:
        bindings.append(rng.integers(0, HW16.n_tiles, size=cl.n_clusters))
    orders_list = [
        project_order(order, b, HW16.n_tiles) for b in bindings
    ]

    t0 = time.perf_counter()
    rep = batch_execute(app, np.array(bindings), HW16, orders_list,
                        backend="edges")
    t_batched = time.perf_counter() - t0

    t0 = time.perf_counter()
    serial = np.array([
        SelfTimedExecutor(app, b, HW16, orders=o)
        .run(iterations=sim_iterations).period
        for b, o in zip(bindings, orders_list)
    ])
    t_serial = time.perf_counter() - t0

    # fidelity: heapq period amortizes the pipeline-fill transient over the
    # run, so compare loosely; the engine value is the exact steady state
    ok_rows = serial > 0
    rel = np.abs(rep.periods[ok_rows] - serial[ok_rows]) / serial[ok_rows]
    speedup = t_serial / max(t_batched, 1e-12)
    rows = [
        ("metric", "value"),
        ("candidates", len(bindings)),
        ("actors", app.n_actors),
        ("t_batched_s", f"{t_batched:.4f}"),
        ("t_heapq_serial_s", f"{t_serial:.4f}"),
        ("speedup", f"{speedup:.1f}x"),
        ("max_rel_dev_vs_heapq", f"{rel.max():.2e}"),
        ("best_candidate", int(np.argmin(np.where(
            rep.periods > 0, rep.periods, np.inf)))),
    ]
    payload = {
        "n_candidates": len(bindings),
        "t_batched_s": t_batched,
        "t_heapq_serial_s": t_serial,
        "speedup_batched_vs_heapq": speedup,
        "max_rel_dev_vs_heapq": float(rel.max()),
        "periods_batched": rep.periods.tolist(),
        "periods_heapq": serial.tolist(),
    }
    ok = speedup >= 3.0
    return rows, payload, ok


# ======================================================================
def run(out_path: str = "BENCH_admission.json", *, n_apps: int = 6,
        rounds: int = 4, n_candidates: int = 16):
    """Run both sections and write the trajectory file.

    Returns ``(rows, summary, ok)`` in the benchmarks/run.py convention.
    """
    t_rows, t_payload = trajectory_bench(n_apps=n_apps, rounds=rounds)
    s_rows, s_payload, ok = speedup_bench(n_candidates=n_candidates)
    from .common import write_bench
    write_bench(out_path,
                {"trajectory_bench": t_payload, "speedup_bench": s_payload})
    rows = t_rows + [("--", "--")] + s_rows
    summary = (
        f"{t_payload['n_admissions']} admissions at "
        f"{t_payload['admissions_per_sec']:.1f}/s; batched scoring of "
        f"{s_payload['n_candidates']} candidates "
        f"{s_payload['speedup_batched_vs_heapq']:.1f}x vs heapq loop "
        f"(target >= 3x: {'PASS' if ok else 'MISS'}); wrote {out_path}"
    )
    return rows, summary, ok


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_admission.json")
    ap.add_argument("--apps", type=int, default=6)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--candidates", type=int, default=16)
    args = ap.parse_args()

    if args.candidates < 16:
        ap.error("--candidates must be >= 16 (the acceptance target scores "
                 "at least 16 bindings)")
    rows, summary, ok = run(
        args.out, n_apps=args.apps, rounds=args.rounds,
        n_candidates=args.candidates,
    )
    print("# admission")
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", summary)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
