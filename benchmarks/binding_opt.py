"""Throughput-in-the-loop binding optimizer benchmark (closing §4.2's loop).

  PYTHONPATH=src python -m benchmarks.binding_opt             # all 8 apps
  PYTHONPATH=src python -m benchmarks.binding_opt --quick     # 3 small apps
  PYTHONPATH=src python -m benchmarks.run binding_opt         # via the runner

Two sections, both recorded into ``BENCH_binding_opt.json``:

  1. *Optimizer vs heuristics* — for every Table-1 application, run
     :func:`repro.core.optimize.optimize_binding` (>= 64-candidate
     generations, each scored by ONE batched engine call) and compare the
     exact steady-state period against the three §4.2/§6.3 heuristic
     binders.  Acceptance: strictly better than the best heuristic on
     >= 6 of the 8 apps and never worse on any (the seeds are in the
     final scoring pool, so "never worse" is structural).
  2. *Population scaling* — wall-clock per generation as the population
     grows (one EdgeStack build + one ``mcr_batch`` per generation, so
     per-candidate cost should fall with batch size).
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
    build_app,
    optimize_binding,
    partition_greedy,
    single_tile_order,
)

QUICK_APPS = ("ImgSmooth", "MLP-MNIST", "CNN-MNIST")
BEAT_TOL = 1e-6       # relative period margin that counts as a win


# ======================================================================
# section 1: optimizer vs the three heuristic binders, per application
# ======================================================================
def optimizer_bench(apps, *, population=64, generations=8, rng_seed=0):
    """Optimize every app's binding; compare against the heuristic seeds."""
    per_app = []
    for name in apps:
        cl = partition_greedy(build_app(name), DYNAP_SE)
        order, _ = single_tile_order(cl, DYNAP_SE)
        t0 = time.perf_counter()
        rep = optimize_binding(
            cl, DYNAP_SE, single_order=order,
            population=population, generations=generations, rng_seed=rng_seed,
        )
        wall = time.perf_counter() - t0
        gen_walls = [h.wall_s for h in rep.history]
        per_app.append({
            "app": name,
            "n_clusters": int(cl.n_clusters),
            "period_optimized_us": rep.period,
            "period_seeds_us": rep.seed_periods,
            "period_best_seed_us": rep.best_seed_period,
            "period_ours_us": rep.seed_periods["ours"],
            "improvement_vs_best_seed": rep.improvement,
            "improvement_vs_ours": (
                (rep.seed_periods["ours"] - rep.period)
                / rep.seed_periods["ours"]
            ),
            "beat_best_seed": bool(rep.improvement > BEAT_TOL),
            "never_worse": bool(rep.period <= rep.best_seed_period * (1 + 1e-9)),
            "wall_s": wall,
            "wall_per_generation_s": float(np.mean(gen_walls)),
            "n_stack_builds": rep.n_stack_builds,
            "one_build_per_generation": bool(
                rep.n_stack_builds == generations + 1
            ),
        })
    wins = sum(a["beat_best_seed"] for a in per_app)
    all_never_worse = all(a["never_worse"] for a in per_app)
    rows = [("app", "clusters", "best_heuristic_us", "optimized_us",
             "improv_vs_best", "improv_vs_ours", "wall_s", "s_per_gen")]
    for a in per_app:
        rows.append((
            a["app"], a["n_clusters"],
            f"{a['period_best_seed_us']:.4f}",
            f"{a['period_optimized_us']:.4f}",
            f"{a['improvement_vs_best_seed'] * 100:.3f}%",
            f"{a['improvement_vs_ours'] * 100:.3f}%",
            f"{a['wall_s']:.1f}", f"{a['wall_per_generation_s']:.2f}",
        ))
    payload = {
        "population": population,
        "generations": generations,
        "rng_seed": rng_seed,
        "beat_tolerance_rel": BEAT_TOL,
        "apps": per_app,
        "wins": int(wins),
        "n_apps": len(per_app),
        "all_never_worse": all_never_worse,
    }
    return rows, payload, wins, all_never_worse


# ======================================================================
# section 2: wall-clock per generation vs population size
# ======================================================================
def scaling_bench(app_name="CNN-MNIST", *, populations=(16, 32, 64, 128),
                  generations=2, rng_seed=0):
    """One batched call scores the whole generation: per-candidate cost
    must fall as the population grows."""
    cl = partition_greedy(build_app(app_name), DYNAP_SE)
    order, _ = single_tile_order(cl, DYNAP_SE)
    points = []
    for pop in populations:
        rep = optimize_binding(
            cl, DYNAP_SE, single_order=order,
            population=pop, generations=generations, rng_seed=rng_seed,
        )
        per_gen = float(np.mean([h.wall_s for h in rep.history]))
        points.append({
            "population": pop,
            "wall_per_generation_s": per_gen,
            "wall_per_candidate_ms": 1e3 * per_gen / pop,
            "period_us": rep.period,
        })
    rows = [("population", "s_per_gen", "ms_per_candidate", "period_us")]
    for p in points:
        rows.append((
            p["population"], f"{p['wall_per_generation_s']:.3f}",
            f"{p['wall_per_candidate_ms']:.2f}", f"{p['period_us']:.4f}",
        ))
    payload = {"app": app_name, "generations": generations, "points": points}
    return rows, payload


# ======================================================================
def run(out_path: str = "BENCH_binding_opt.json", *, apps=APP_NAMES,
        population: int = 64, generations: int = 8,
        scaling_app: str = "CNN-MNIST"):
    """Run both sections and write ``BENCH_binding_opt.json``.

    Returns ``(rows, summary, ok)`` in the benchmarks/run.py convention;
    ``ok`` is the acceptance check (wins on >= 6 of the 8 Table-1 apps —
    scaled proportionally for --quick runs — and never worse on any).
    """
    o_rows, o_payload, wins, never_worse = optimizer_bench(
        apps, population=population, generations=generations
    )
    s_rows, s_payload = scaling_bench(scaling_app, generations=2)
    from .common import write_bench
    write_bench(out_path,
                {"optimizer_bench": o_payload, "scaling_bench": s_payload})
    need = max(1, (6 * len(apps)) // 8)      # 6-of-8, scaled for --quick
    ok = wins >= need and never_worse
    rows = o_rows + [("--",) * 8] + s_rows
    summary = (
        f"optimizer beats best heuristic on {wins}/{len(apps)} apps "
        f"(target >= {need}: {'PASS' if wins >= need else 'MISS'}); "
        f"never worse: {never_worse}; "
        f"{population}-candidate generations, one EdgeStack build each; "
        f"wrote {out_path}"
    )
    return rows, summary, ok


def main() -> None:
    """CLI entry point (see module docstring for usage)."""
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_binding_opt.json")
    ap.add_argument("--quick", action="store_true",
                    help="3 small apps + smaller scaling app")
    ap.add_argument("--population", type=int, default=64)
    ap.add_argument("--generations", type=int, default=8)
    args = ap.parse_args()

    if args.population < 64:
        ap.error("--population must be >= 64 (the acceptance target scores "
                 ">= 64-candidate generations)")
    apps = QUICK_APPS if args.quick else APP_NAMES
    scaling_app = "MLP-MNIST" if args.quick else "CNN-MNIST"
    rows, summary, ok = run(
        args.out, apps=apps, population=args.population,
        generations=args.generations, scaling_app=scaling_app,
    )
    print("# binding_opt")
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", summary)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
