"""Chip-level objective benchmark: energy model + multi-app joint placement.

  PYTHONPATH=src python -m benchmarks.energy              # all 8 apps
  PYTHONPATH=src python -m benchmarks.energy --smoke      # CI-sized run
  PYTHONPATH=src python -m benchmarks.run energy          # via the runner

Two sections, both recorded into ``BENCH_energy.json``:

  1. *Isolated vs joint churn* — the same deterministic admission churn
     (admit / finish / evict rounds on a 16-tile chip) served twice by an
     :class:`~repro.core.runtime.AdmissionController`: once with
     ``placement="isolated"`` (each admission optimized alone, the PR-2
     behaviour) and once with ``placement="joint"`` (every admit/evict
     re-optimizes ALL resident bindings as one union EdgeStack).  After
     every operation the chip steady state (union period, chip energy) is
     snapshotted; acceptance: joint strictly improves mean chip
     throughput OR mean chip energy — it can never be worse on the scored
     objective, because the isolated placement seeds each rebalance.
  2. *Pareto front per app* — ``optimize_binding(objective="pareto")`` on
     every Table-1 application: the exact (period, energy) front, plus
     the structural check that the front's best period is never worse
     than the heuristic seeds'.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import (
    APP_NAMES,
    DYNAP_SE,
    AdmissionController,
    AdmissionError,
    HardwareConfig,
    build_app,
    optimize_binding,
    partition_greedy,
    small_app,
)
import dataclasses

HW16 = dataclasses.replace(DYNAP_SE, n_tiles=16)
SMOKE_APPS = 3          # synthetic small apps for --smoke


def _churn_apps(smoke: bool, n_apps: int):
    """The tenant set: Table-1 apps, or small synthetic ones for --smoke."""
    if smoke:
        apps = []
        for i in range(SMOKE_APPS):
            snn = small_app(300, 5200, seed=40 + i)
            snn.name = f"smoke{i}"
            apps.append(snn)
        return apps
    return [build_app(name) for name in APP_NAMES[:n_apps]]


def _churn_hw(smoke: bool) -> HardwareConfig:
    """16 tiles for the Table-1 churn; 8 (a 2x4 rectangular mesh) for
    --smoke so the synthetic tenants actually contend — joint placement
    has nothing to fix on an uncontended chip."""
    return dataclasses.replace(DYNAP_SE, n_tiles=8) if smoke else HW16


def _drive_churn(ctl: AdmissionController, apps, rounds: int, seed: int):
    """One deterministic churn schedule; returns per-operation snapshots.

    The schedule (requests, finish/evict picks) depends only on the rng
    seed and the app list — NOT on admission outcomes — so the isolated
    and joint controllers serve identical workloads and their snapshots
    compare one-to-one.  ``apps`` may be SNNs or pre-clustered apps.
    """
    rng = np.random.default_rng(seed)
    names = [getattr(a, "name", None) or a.snn.name for a in apps]
    for a in apps:
        ctl.register(a)
    snapshots = []

    def snap(op: str):
        m = ctl.chip_metrics()
        snapshots.append({
            "op": op,
            "n_resident": 0 if m is None else m["n_resident"],
            "chip_period": np.nan if m is None else m["chip_period"],
            "chip_throughput": 0.0 if m is None else m["chip_throughput"],
            "chip_energy": np.nan if m is None else m["chip_energy"],
            "chip_noc_traffic": (
                np.nan if m is None else m["chip_noc_traffic"]
            ),
        })

    for _ in range(rounds):
        for name in names:
            req = int(rng.integers(2, 5))
            try:
                ctl.admit(name, n_tiles_request=req)
            except AdmissionError:
                pass
            snap(f"admit:{name}")
        drop = [names[i] for i in rng.permutation(len(names))]
        for name in drop[: len(drop) // 2]:          # finish half...
            if name in ctl.running():
                ctl.finish(name)
            snap(f"finish:{name}")
        for name in drop[len(drop) // 2 : (3 * len(drop)) // 4]:
            if name in ctl.running():                # ...evict a quarter
                ctl.evict(name)
            snap(f"evict:{name}")
    return snapshots


def churn_bench(*, smoke: bool = False, n_apps: int = 8, rounds: int = 2,
                joint_budget=(2, 12), seed: int = 0):
    """Serve the same churn isolated and joint; compare chip metrics."""
    # partition once, share the clustered apps across both controllers
    # (register() accepts ClusteredSNN, so neither pays Alg. 1 twice)
    hw = _churn_hw(smoke)
    apps = [
        partition_greedy(snn, hw) for snn in _churn_apps(smoke, n_apps)
    ]
    results = {}
    walls = {}
    for placement in ("isolated", "joint"):
        ctl = AdmissionController(
            hw, placement=placement, joint_budget=joint_budget,
            track_chip_metrics=True,
        )
        t0 = time.perf_counter()
        snaps = _drive_churn(ctl, apps, rounds, seed)
        walls[placement] = time.perf_counter() - t0
        results[placement] = {
            "snapshots": snaps,
            "n_rebalances": sum(
                1 for e in ctl.events if e.kind == "rebalance"
            ),
            "trajectory": ctl.trajectory(),
        }

    # mean over the snapshots where BOTH runs had residents (one-to-one
    # comparable: the schedule is outcome-independent)
    iso, joi = results["isolated"]["snapshots"], results["joint"]["snapshots"]
    assert len(iso) == len(joi), "churn schedules diverged"
    both = [
        (a, b) for a, b in zip(iso, joi)
        if a["n_resident"] > 0 and b["n_resident"] > 0
    ]
    thr_iso = float(np.mean([a["chip_throughput"] for a, _ in both]))
    thr_joi = float(np.mean([b["chip_throughput"] for _, b in both]))
    e_iso = float(np.mean([a["chip_energy"] for a, _ in both]))
    e_joi = float(np.mean([b["chip_energy"] for _, b in both]))
    thr_gain = (thr_joi - thr_iso) / max(thr_iso, 1e-300)
    e_gain = (e_iso - e_joi) / max(e_iso, 1e-300)
    ok = thr_joi > thr_iso * (1 + 1e-9) or e_joi < e_iso * (1 - 1e-9)

    rows = [
        ("metric", "isolated", "joint", "gain"),
        ("mean_chip_throughput", f"{thr_iso:.6e}", f"{thr_joi:.6e}",
         f"{thr_gain:+.2%}"),
        ("mean_chip_energy_pj", f"{e_iso:.1f}", f"{e_joi:.1f}",
         f"{e_gain:+.2%}"),
        ("rebalances", 0, results["joint"]["n_rebalances"], ""),
        ("wall_s", f"{walls['isolated']:.2f}", f"{walls['joint']:.2f}", ""),
    ]
    payload = {
        "n_apps": len(apps),
        "rounds": rounds,
        "joint_budget": list(joint_budget),
        "mean_chip_throughput": {"isolated": thr_iso, "joint": thr_joi},
        "mean_chip_energy_pj": {"isolated": e_iso, "joint": e_joi},
        "throughput_gain": thr_gain,
        "energy_gain": e_gain,
        "joint_improves": bool(ok),
        "wall_s": walls,
        "isolated": results["isolated"],
        "joint": results["joint"],
    }
    return rows, payload, ok


# ======================================================================
# section 2: (period, energy) Pareto front per application
# ======================================================================
def pareto_bench(apps=None, *, population: int = 24, generations: int = 3,
                 rng_seed: int = 0, smoke: bool = False):
    """Per-app exact Pareto fronts from the pareto-objective optimizer."""
    per_app = []
    ok = True
    if apps is None:
        apps = (
            [s.name for s in _churn_apps(True, SMOKE_APPS)] if smoke
            else APP_NAMES
        )
    for name in apps:
        snn = (
            small_app(170, 2100, seed=40 + int(name[-1]))
            if smoke else build_app(name)
        )
        if smoke:
            snn.name = name
        cl = partition_greedy(snn, DYNAP_SE)
        t0 = time.perf_counter()
        rep = optimize_binding(
            cl, DYNAP_SE, population=population, generations=generations,
            rng_seed=rng_seed, objective="pareto",
        )
        never_worse = rep.period <= rep.best_seed_period * (1 + 1e-9)
        ok = ok and never_worse and len(rep.front) >= 1
        per_app.append({
            "app": name,
            "n_clusters": int(cl.n_clusters),
            "front": [
                {"period_us": pt.period, "energy_pj": pt.energy}
                for pt in rep.front
            ],
            "best_period_us": rep.period,
            "best_seed_period_us": rep.best_seed_period,
            "min_energy_pj": min(pt.energy for pt in rep.front),
            "seed_energies_pj": rep.seed_energies,
            "never_worse_than_seeds": bool(never_worse),
            "wall_s": time.perf_counter() - t0,
        })
    rows = [("app", "clusters", "front_size", "best_period_us",
             "min_energy_pj", "never_worse")]
    for r in per_app:
        rows.append((
            r["app"], r["n_clusters"], len(r["front"]),
            f"{r['best_period_us']:.4f}", f"{r['min_energy_pj']:.1f}",
            r["never_worse_than_seeds"],
        ))
    payload = {"population": population, "generations": generations,
               "apps": per_app}
    return rows, payload, ok


# ======================================================================
def run(out_path: str = "BENCH_energy.json", *, smoke: bool = False,
        n_apps: int = 8, rounds: int = 2):
    """Run both sections and write the JSON artifact.

    Returns ``(rows, summary, ok)`` in the benchmarks/run.py convention.
    """
    c_rows, c_payload, c_ok = churn_bench(
        smoke=smoke, n_apps=n_apps, rounds=rounds,
    )
    p_rows, p_payload, p_ok = pareto_bench(smoke=smoke)
    from .common import write_bench
    write_bench(out_path,
                {"churn_bench": c_payload, "pareto_bench": p_payload})
    rows = c_rows + [("--", "--", "--", "--")] + p_rows
    ok = c_ok and p_ok
    summary = (
        f"joint vs isolated churn: throughput "
        f"{c_payload['throughput_gain']:+.2%}, energy "
        f"{c_payload['energy_gain']:+.2%} "
        f"(improves: {'PASS' if c_ok else 'MISS'}); pareto fronts on "
        f"{len(p_payload['apps'])} apps, never worse than seeds: "
        f"{'PASS' if p_ok else 'MISS'}; wrote {out_path}"
    )
    return rows, summary, ok


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_energy.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: 3 synthetic apps, 1 round")
    ap.add_argument("--apps", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args()

    rows, summary, ok = run(
        args.out, smoke=args.smoke,
        n_apps=args.apps, rounds=1 if args.smoke else args.rounds,
    )
    print("# energy")
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", summary)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
