"""Chip-scale stress harness: Zipf tenant churn on a 32x32 mesh.

  PYTHONPATH=src python -m benchmarks.stress              # 1024-tile run
  PYTHONPATH=src python -m benchmarks.stress --smoke      # 12 tenants, 8x8
  PYTHONPATH=src python -m benchmarks.run stress          # via the runner

Open-loop arrival/departure churn of synthetic Table-1-fit tenants
(:mod:`repro.core.workloads`) against a joint-placement
:class:`~repro.core.runtime.AdmissionController` with region-scoped
incremental rebalancing: each event draws a tenant from a Zipf popularity
distribution and admits it when absent, evicts it when resident — hot
tenants cycle, the tail accumulates residents.  Recorded into
``BENCH_stress.json``:

  * sustained admissions/s over the event loop;
  * p50/p99 per-event joint-placement (rebalance) latency — region-scoped
    rebalances keep this bounded by the REGION size, not the resident
    count;
  * the never-regress check: every rebalance's chip throughput vs. the
    chip state just before it (the seeding invariant, per event);
  * throughput retention vs. FULL re-optimization at checkpoints: the
    event loop runs pure region-scoped, then a full-union re-optimization
    is forced outside the timed loop and the before/after chip throughput
    ratio is recorded (1.0 = region placement had lost nothing).

Acceptance (full run): >= 64 concurrent residents on the 32x32 mesh,
per-event joint-placement p99 < 1 s, no rebalance ever regresses chip
throughput, checkpoint retention >= 0.95.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import (
    DYNAP_SE,
    DYNAP_SE_1024,
    AdmissionController,
    AdmissionError,
)
from repro.core.workloads import workload_suite

#: Zipf popularity exponent of the tenant draw (p ~ rank^-ZIPF_S).
ZIPF_S = 1.1


def _zipf_probs(n: int, s: float = ZIPF_S) -> np.ndarray:
    r = np.arange(1, n + 1, dtype=np.float64) ** -s
    return r / r.sum()


def _tiles_request(n_clusters: int) -> int:
    """Small per-tenant footprint so hundreds of tenants fit the mesh."""
    return max(1, min(4, n_clusters))


def _percentiles(xs: list[float]) -> tuple[float, float]:
    if not xs:
        return 0.0, 0.0
    arr = np.asarray(xs)
    return float(np.percentile(arr, 50)), float(np.percentile(arr, 99))


def stress_bench(
    *,
    smoke: bool = False,
    n_tenants: int = 224,
    n_events: int = 640,
    scale: float = 0.06,
    joint_budget: tuple[int, int] = (1, 6),
    n_checkpoints: int = 2,
    seed: int = 0,
):
    """Run the churn and return ``(rows, payload, ok)``.

    ``--smoke`` shrinks to 12 tenants / 24 events on an 8x8 (64-tile)
    mesh — the CI tier-1 configuration.
    """
    if smoke:
        hw = dataclasses.replace(DYNAP_SE, n_tiles=64)
        n_tenants, n_events, n_checkpoints = 12, 36, 1
    else:
        hw = DYNAP_SE_1024
    mesh = hw.mesh_shape

    t0 = time.perf_counter()
    tenants = workload_suite(n_tenants, seed=seed, scale=scale)
    ctl = AdmissionController(
        hw,
        placement="joint",
        joint_budget=joint_budget,
        # the bench forces full re-optimizations at explicit checkpoints
        # OUTSIDE the timed loop; per-event latency stays region-scoped
        full_rebalance_every=0,
    )
    requests = {}
    for snn in tenants:
        art = ctl.register(snn)
        requests[snn.name] = _tiles_request(art.clustered.n_clusters)
    design_wall_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed + 1)
    probs = _zipf_probs(n_tenants)
    names = [s.name for s in tenants]

    rows = [(
        "event", "kind", "tenant", "residents", "wall_s",
        "rebalance_wall_s", "rebalance_scope", "region_apps",
        "chip_throughput",
    )]
    admits = evicts = rejects = 0
    residents_track: list[int] = []
    event_loop_t0 = time.perf_counter()
    for ev in range(n_events):
        name = names[int(rng.choice(n_tenants, p=probs))]
        n_before = len(ctl.events)
        t_ev = time.perf_counter()
        if name in ctl.state.allocated:
            ctl.evict(name)
            kind = "evict"
            evicts += 1
        else:
            try:
                ctl.admit(name, n_tiles_request=requests[name])
                kind = "admit"
                admits += 1
            except AdmissionError:
                kind = "reject"
                rejects += 1
        wall = time.perf_counter() - t_ev
        new_events = ctl.events[n_before:]
        reb = [e for e in new_events if e.kind == "rebalance"]
        chip_thr = new_events[-1].chip_throughput if new_events else 0.0
        residents_track.append(len(ctl.state.allocated))
        rows.append((
            ev, kind, name, len(ctl.state.allocated), round(wall, 4),
            round(reb[-1].wall_s, 4) if reb else 0.0,
            reb[-1].scope if reb else "",
            reb[-1].region_apps if reb else 0,
            chip_thr,
        ))
    event_loop_s = time.perf_counter() - event_loop_t0
    n_loop_events = len(ctl.events)   # checkpoint rebalances come after

    # -- never-regress: each rebalance vs. the chip state just before it
    never_regressed = True
    prev_thr = None
    for e in ctl.events:
        if e.chip_throughput > 0:
            if (
                e.kind == "rebalance"
                and prev_thr is not None
                and prev_thr > 0
                and e.chip_throughput < prev_thr * (1 - 1e-6)
            ):
                never_regressed = False
            prev_thr = e.chip_throughput
        elif e.kind in ("admit", "evict", "finish"):
            prev_thr = e.chip_throughput or None

    # -- retention checkpoints: force a FULL re-optimization and compare
    retention: list[float] = []
    for _ in range(max(n_checkpoints, 0)):
        if len(ctl.state.allocated) < 2:
            break
        before = ctl.chip_metrics()
        t_full = time.perf_counter()
        ctl._rebalance_full()
        full_wall = time.perf_counter() - t_full
        after = ctl.chip_metrics()
        if before and after and after["chip_throughput"] > 0:
            retention.append(
                before["chip_throughput"] / after["chip_throughput"]
            )
        rows.append((
            "checkpoint", "full_rebalance", "*",
            len(ctl.state.allocated), round(full_wall, 4),
            round(full_wall, 4), "full", len(ctl.state.allocated),
            after["chip_throughput"] if after else 0.0,
        ))

    # latency stats cover every rebalance the EVENT LOOP ran (region and
    # full-fallback alike) — checkpoint fulls happen outside the loop
    reb_events = [
        e for e in ctl.events[:n_loop_events] if e.kind == "rebalance"
    ]
    region_walls = [e.wall_s for e in reb_events if e.scope == "region"]
    event_walls = [e.wall_s for e in reb_events] or [0.0]
    p50, p99 = _percentiles(event_walls)
    r50, r99 = _percentiles(region_walls)
    max_res = max(residents_track, default=0)
    retention_min = min(retention, default=1.0)

    min_residents = 64 if not smoke else 6
    ok = (
        max_res >= min_residents
        and p99 < 1.0
        and never_regressed
        and retention_min >= 0.95
    )
    summary = {
        "mesh": list(mesh),
        "n_tiles": hw.n_tiles,
        "n_tenants": n_tenants,
        "n_events": n_events,
        "tenant_scale": scale,
        "zipf_s": ZIPF_S,
        "joint_budget": list(joint_budget),
        "design_wall_s": round(design_wall_s, 2),
        "event_loop_s": round(event_loop_s, 2),
        "admits": admits,
        "evicts": evicts,
        "rejects": rejects,
        "admissions_per_s": (
            round(admits / event_loop_s, 3) if event_loop_s > 0 else 0.0
        ),
        "max_residents": max_res,
        "mean_residents": round(float(np.mean(residents_track)), 1),
        "rebalances_region": sum(
            1 for e in reb_events if e.scope == "region"
        ),
        "rebalances_full": sum(1 for e in reb_events if e.scope == "full"),
        "event_rebalance_p50_s": round(p50, 4),
        "event_rebalance_p99_s": round(p99, 4),
        "region_rebalance_p50_s": round(r50, 4),
        "region_rebalance_p99_s": round(r99, 4),
        "never_regressed": never_regressed,
        "retention_vs_full": [round(r, 4) for r in retention],
        "retention_min": round(retention_min, 4),
        "ok": ok,
    }
    return rows, summary, ok


def run(out_path: str = "BENCH_stress.json", *, smoke: bool = False,
        **kw):
    rows, summary, ok = stress_bench(smoke=smoke, **kw)
    from .common import write_bench
    write_bench(out_path, {"stress_bench": summary})
    return rows, summary, ok


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_stress.json")
    ap.add_argument("--smoke", action="store_true",
                    help="12 tenants on an 8x8 mesh (CI tier-1)")
    ap.add_argument("--tenants", type=int, default=224)
    ap.add_argument("--events", type=int, default=640)
    ap.add_argument("--scale", type=float, default=0.06)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rows, summary, ok = run(
        args.out, smoke=args.smoke, n_tenants=args.tenants,
        n_events=args.events, scale=args.scale, seed=args.seed,
    )
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", json.dumps(summary))
    print("OK" if ok else "FAILED")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
