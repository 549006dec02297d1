"""Compile front-end latency benchmark (array-native front-end).

  PYTHONPATH=src python -m benchmarks.compile_latency            # full run
  PYTHONPATH=src python -m benchmarks.compile_latency --smoke    # CI smoke
  PYTHONPATH=src python -m benchmarks.run compile                # via runner

Three sections, all recorded into ``BENCH_compile.json``:

  1. *Front-end* — per-stage wall-clock of the OLD serial front-end
     (scalar Alg. 1, heapq FCFS order construction, per-graph Howard) vs
     the NEW array-native one (wave-based partitioner, dense batched FCFS
     constructor, batched engine analysis) on the Table-1 apps.
     Acceptance: >= 5x end-to-end on the largest app, identical clusters,
     identical static orders, periods within 1e-6.
  2. *Admission* — warm multi-tenant admission throughput of the new
     front-end vs the ``BENCH_admission.json`` baseline.  Acceptance:
     >= 2x admissions/sec.
  3. *Compile cache* — shape-bucket hit rates under repeated admissions
     and optimizer generations (the EdgeStack shapes the XLA cache sees).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import (
    DYNAP_SE,
    AdmissionController,
    analyze_throughput,
    batch_execute,
    bind_ours,
    build_app,
    build_static_orders,
    build_static_orders_batch,
    compile_cache_stats,
    optimize_binding,
    partition_greedy,
    partition_greedy_reference,
    reset_compile_cache_stats,
    sdfg_from_clusters,
    single_tile_order,
    small_app,
)
from repro.core.apps import APP_SPECS

#: trajectory-bench admissions/sec recorded before this PR (the stored
#: BENCH_admission.json baseline; used when the file is absent)
FALLBACK_BASELINE_ADMISSIONS_PER_SEC = 36.85

SPEEDUP_TARGET = 5.0
ADMISSION_TARGET = 2.0


# ======================================================================
# section 1: old vs new front-end, per stage, per app
# ======================================================================
def frontend_app_bench(name: str) -> dict:
    """Time every compile stage of one app through both front-ends."""
    snn = build_app(name)

    # -- old: scalar partitioner, heapq orders, per-graph Howard --------
    t0 = time.perf_counter()
    cl_old = partition_greedy_reference(snn, DYNAP_SE)
    t_part_old = time.perf_counter() - t0
    app = sdfg_from_clusters(cl_old, hw=DYNAP_SE)
    t0 = time.perf_counter()
    bres = bind_ours(cl_old, DYNAP_SE)
    t_bind_old = time.perf_counter() - t0
    t0 = time.perf_counter()
    orders_old, _ = build_static_orders(app, bres.binding, DYNAP_SE,
                                        iterations=12)
    t_ord_old = time.perf_counter() - t0
    _, t_s1t_old = single_tile_order(cl_old, DYNAP_SE, method="heapq")
    t0 = time.perf_counter()
    thr_old = analyze_throughput(app, bres.binding, DYNAP_SE, orders_old)
    t_an_old = time.perf_counter() - t0

    # -- new: wave partitioner, dense batched FCFS, batched engine ------
    t0 = time.perf_counter()
    cl_new = partition_greedy(snn, DYNAP_SE)
    t_part_new = time.perf_counter() - t0
    app_new = sdfg_from_clusters(cl_new, hw=DYNAP_SE)
    t0 = time.perf_counter()
    bres_new = bind_ours(cl_new, DYNAP_SE)
    t_bind_new = time.perf_counter() - t0
    t0 = time.perf_counter()
    orders_new = build_static_orders_batch(app_new, bres_new.binding,
                                           DYNAP_SE)[0]
    t_ord_new = time.perf_counter() - t0
    _, t_s1t_new = single_tile_order(cl_new, DYNAP_SE)
    t0 = time.perf_counter()
    rep = batch_execute(app_new, bres_new.binding, DYNAP_SE, [orders_new],
                        backend="edges")
    thr_new = float(rep.throughputs[0])
    t_an_new = time.perf_counter() - t0

    old = {
        "partition_s": t_part_old, "bind_s": t_bind_old,
        "orders_s": t_ord_old, "single_tile_order_s": t_s1t_old,
        "analyze_s": t_an_old,
        "total_s": t_part_old + t_bind_old + t_ord_old + t_s1t_old + t_an_old,
    }
    new = {
        "partition_s": t_part_new, "bind_s": t_bind_new,
        "orders_s": t_ord_new, "single_tile_order_s": t_s1t_new,
        "analyze_s": t_an_new,
        "total_s": t_part_new + t_bind_new + t_ord_new + t_s1t_new + t_an_new,
    }
    # correctness contracts:
    #  * clusters bit-identical to the scalar Algorithm 1,
    #  * orders == the §4.4 step-2 oracle (heapq FCFS, first firings),
    #  * engine period on the SAME orders == per-graph Howard to 1e-6.
    # The old front-end's 12-iteration heapq horizon may legitimately
    # record a different (equally valid) schedule when repeat firings
    # contend — its throughput is reported as an informational ratio.
    from repro.core import SelfTimedExecutor

    oracle = SelfTimedExecutor(app_new, bres_new.binding, DYNAP_SE).run(
        iterations=1
    ).tile_orders
    thr_howard = analyze_throughput(app_new, bres_new.binding, DYNAP_SE,
                                    orders_new)
    engine_dev = abs(thr_new - thr_howard) / max(thr_howard, 1e-300)
    return {
        "app": name,
        "n_neurons": snn.n_neurons,
        "n_clusters": cl_new.n_clusters,
        "old": old,
        "new": new,
        "speedup": old["total_s"] / max(new["total_s"], 1e-12),
        "clusters_identical": bool(
            np.array_equal(cl_new.cluster_of, cl_old.cluster_of)
        ),
        "orders_match_oracle": orders_new == oracle,
        "orders_identical_to_12iter_heapq": orders_new == orders_old,
        "engine_vs_howard_rel_dev": engine_dev,
        "throughput_vs_old": thr_new / max(thr_old, 1e-300),
        "throughput": thr_new,
    }


def frontend_bench(apps: list[str]) -> dict:
    records = [frontend_app_bench(name) for name in apps]
    largest = max(records, key=lambda r: r["n_neurons"])
    return {
        "apps": records,
        "largest_app": largest["app"],
        "largest_speedup": largest["speedup"],
        "target_speedup": SPEEDUP_TARGET,
        "all_clusters_identical": all(r["clusters_identical"] for r in records),
        "all_orders_match_oracle": all(
            r["orders_match_oracle"] for r in records
        ),
        "all_periods_close": all(
            r["engine_vs_howard_rel_dev"] <= 1e-6 for r in records
        ),
        "pass": largest["speedup"] >= SPEEDUP_TARGET,
    }


# ======================================================================
# section 2: admission throughput vs the stored baseline
# ======================================================================
def admission_bench(baseline_path: str = "BENCH_admission.json",
                    *, rounds: int = 8) -> dict:
    from .admission import trajectory_bench

    baseline = FALLBACK_BASELINE_ADMISSIONS_PER_SEC
    if os.path.exists(baseline_path):
        try:
            with open(baseline_path) as fh:
                baseline = json.load(fh)["trajectory_bench"][
                    "admissions_per_sec"
                ]
        except (KeyError, json.JSONDecodeError):
            pass
    trajectory_bench(n_apps=2, rounds=1, seed=99)   # warm jax + code paths
    _, payload = trajectory_bench(n_apps=6, rounds=rounds)
    aps = payload["admissions_per_sec"]
    return {
        "admissions_per_sec": aps,
        "n_admissions": payload["n_admissions"],
        "baseline_admissions_per_sec": baseline,
        "ratio_vs_baseline": aps / max(baseline, 1e-12),
        "target_ratio": ADMISSION_TARGET,
        "pass": aps / max(baseline, 1e-12) >= ADMISSION_TARGET,
    }


# ======================================================================
# section 3: shape-bucket compile-cache hit rates
# ======================================================================
def cache_bench(*, n_admission_cycles: int = 6) -> dict:
    """Repeated admissions + optimizer generations through one engine.

    The OrderBatch order representation keeps the stacked (B, n, E) shape
    invariant across optimizer generations, and the admission controller
    re-admits with the same candidate-subset count — so after the first
    trace every analysis call lands on a previously-seen bucket.
    """
    snn = small_app(240, 3000, seed=5)
    snn.name = "cache-app"
    ctl = AdmissionController(DYNAP_SE)
    ctl.register(snn)
    cl = ctl.artifacts[(snn.name, DYNAP_SE)].clustered

    reset_compile_cache_stats()
    for _ in range(n_admission_cycles):
        ctl.admit(snn.name, n_tiles_request=2)
        ctl.finish(snn.name)
    admission_stats = compile_cache_stats().as_dict()

    reset_compile_cache_stats()
    optimize_binding(cl, DYNAP_SE, population=16, generations=4, rng_seed=3)
    optimizer_stats = compile_cache_stats().as_dict()
    reset_compile_cache_stats()
    return {
        "repeated_admissions": admission_stats,
        "optimizer_generations": optimizer_stats,
    }


# ======================================================================
def run(out_path: str = "BENCH_compile.json", *, smoke: bool = False):
    """Run all sections and write the artifact.

    Returns ``(rows, summary, ok)`` in the benchmarks/run.py convention.
    ``smoke=True`` runs the smallest app only and skips the largest-app
    acceptance gate (CI keeps the wall clock short but still exercises
    every stage and the equality checks).
    """
    by_size = sorted(APP_SPECS, key=lambda n: sum(APP_SPECS[n].layer_shape))
    apps = [by_size[0]] if smoke else list(APP_SPECS)
    fe = frontend_bench(apps)
    adm = admission_bench(rounds=2 if smoke else 8)
    cache = cache_bench(n_admission_cycles=2 if smoke else 6)

    rows = [("app", "clusters", "old_total_s", "new_total_s", "speedup",
             "identical_clusters", "orders_match_oracle",
             "engine_vs_howard", "thr_vs_old")]
    for r in fe["apps"]:
        rows.append((
            r["app"], r["n_clusters"], f"{r['old']['total_s']:.3f}",
            f"{r['new']['total_s']:.3f}", f"{r['speedup']:.1f}x",
            r["clusters_identical"], r["orders_match_oracle"],
            f"{r['engine_vs_howard_rel_dev']:.1e}",
            f"{r['throughput_vs_old']:.4f}",
        ))
    rows += [
        ("--",) * 9,
        ("admissions_per_sec", f"{adm['admissions_per_sec']:.1f}"),
        ("admission_ratio_vs_baseline", f"{adm['ratio_vs_baseline']:.1f}x"),
        ("cache_hit_rate_admissions",
         f"{cache['repeated_admissions']['hit_rate']:.2f}"),
        ("cache_hit_rate_optimizer",
         f"{cache['optimizer_generations']['hit_rate']:.2f}"),
    ]

    correctness = (
        fe["all_clusters_identical"]
        and fe["all_orders_match_oracle"]
        and fe["all_periods_close"]
    )
    # smoke (CI) gates on correctness only — wall-clock ratios are too
    # machine-dependent for a shared runner; the full run enforces both
    # acceptance speedups on top
    ok = correctness and (smoke or (adm["pass"] and fe["pass"]))
    payload = {
        "smoke": smoke,
        "frontend_bench": fe,
        "admission_bench": adm,
        "cache_bench": cache,
        "ok": ok,
    }
    from .common import write_bench
    write_bench(out_path, payload)

    gate = "" if smoke else (
        f"largest app {fe['largest_app']} {fe['largest_speedup']:.1f}x "
        f"(target >= {SPEEDUP_TARGET:.0f}x: "
        f"{'PASS' if fe['pass'] else 'MISS'}); "
    )
    summary = (
        f"{gate}admission {adm['admissions_per_sec']:.1f}/s = "
        f"{adm['ratio_vs_baseline']:.1f}x baseline (target >= "
        f"{ADMISSION_TARGET:.0f}x: {'PASS' if adm['pass'] else 'MISS'}); "
        f"clusters identical + orders == oracle + engine == Howard on "
        f"{len(fe['apps'])}/{len(fe['apps'])} apps: "
        f"{'yes' if correctness else 'NO'}; "
        f"cache hit rate {cache['repeated_admissions']['hit_rate']:.0%} "
        f"(admissions) / {cache['optimizer_generations']['hit_rate']:.0%} "
        f"(optimizer); wrote {out_path}"
    )
    return rows, summary, ok


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_compile.json")
    ap.add_argument("--smoke", action="store_true",
                    help="smallest app only; skip the largest-app gate (CI)")
    args = ap.parse_args()
    rows, summary, ok = run(args.out, smoke=args.smoke)
    print("# compile_latency")
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", summary)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
