"""Failure-storm harness: fault/drift recovery on a loaded 32x32 mesh.

  PYTHONPATH=src python -m benchmarks.faults              # 1024-tile run
  PYTHONPATH=src python -m benchmarks.faults --smoke      # 8x8 CI config
  PYTHONPATH=src python -m benchmarks.run faults          # via the runner

Loads the mesh with the PR-6 Zipf churn workload, then drives a Poisson
failure storm (:func:`repro.core.workloads.failure_storm`) through the
controller's fault runtime — tile failures, link throttles, spike-rate
drift, delayed heals — interleaved with continuing tenant churn.  Each
mutation triggers staleness detection and an incremental region
:meth:`~repro.core.runtime.AdmissionController.remap`.  Recorded into
``BENCH_faults.json``:

  * per-fault recovery latency (the full inject call including detection
    and remap), p50/p99;
  * the remap never-regress check: every remap's chip throughput vs. the
    minimally-repaired seed placement it started from
    (``seed_throughput``), per event;
  * dead-binding violations: after EVERY storm event, no resident may
    hold a dead tile (must stay zero);
  * displaced tenants: released with explicit ``"displaced"`` events
    when their component has no alive tile left (never silently lost);
  * throughput retention vs. FULL re-optimization under the SAME
    degraded chip at checkpoints outside the timed loop (>= 0.9 means
    incremental recovery kept >= 90% of what a from-scratch joint
    re-placement would get).

Acceptance (full run): per-fault recovery p99 < 1 s, zero never-regress
violations, zero dead bindings, nonzero recoveries, retention >= 0.9.
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
from repro.core.workloads import failure_storm, workload_suite

from .stress import _percentiles, _tiles_request, _zipf_probs


def _dead_binding_violations(ctl) -> int:
    return sum(
        1
        for ts in ctl.running().values()
        if any(bool(ctl.chip.dead[int(t)]) for t in ts)
    )


def _churn_step(ctl, rng, names, probs, requests) -> str:
    name = names[int(rng.choice(len(names), p=probs))]
    if name in ctl.state.allocated:
        ctl.evict(name)
        return "evict"
    try:
        ctl.admit(name, n_tiles_request=requests[name])
        return "admit"
    except AdmissionError:
        return "reject"


def faults_bench(
    *,
    smoke: bool = False,
    n_tenants: int = 96,
    n_warmup: int = 160,
    n_faults: int = 30,
    churn_per_fault: int = 2,
    scale: float = 0.06,
    joint_budget: tuple[int, int] = (1, 6),
    n_checkpoints: int = 2,
    seed: int = 0,
):
    """Run the storm and return ``(rows, summary, ok)``.

    ``--smoke`` shrinks to 10 tenants / 4 faults on an 8x8 (64-tile)
    mesh — the CI tier-1 configuration.
    """
    if smoke:
        hw = dataclasses.replace(DYNAP_SE, n_tiles=64)
        n_tenants, n_warmup, n_faults = 10, 16, 6
        churn_per_fault, n_checkpoints = 1, 1
        storm_kw = dict(
            tiles_per_fault=1, heal_after=2.0,
            p_throttle=0.15, p_drift=0.15, max_dead_frac=0.15,
        )
    else:
        hw = DYNAP_SE_1024
        storm_kw = dict(
            tiles_per_fault=2, heal_after=4.0,
            p_throttle=0.15, p_drift=0.15, max_dead_frac=0.10,
        )
    mesh = hw.mesh_shape

    t0 = time.perf_counter()
    tenants = workload_suite(n_tenants, seed=seed, scale=scale)
    ctl = AdmissionController(
        hw,
        placement="joint",
        joint_budget=joint_budget,
        full_rebalance_every=0,   # checkpoints force fulls OUTSIDE the loop
    )
    requests = {}
    for snn in tenants:
        art = ctl.register(snn)
        requests[snn.name] = _tiles_request(art.clustered.n_clusters)
    design_wall_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed + 1)
    probs = _zipf_probs(n_tenants)
    names = [s.name for s in tenants]

    # -- phase 1: churn warm-up loads the mesh ---------------------------
    warmup_t0 = time.perf_counter()
    for _ in range(n_warmup):
        _churn_step(ctl, rng, names, probs, requests)
    warmup_s = time.perf_counter() - warmup_t0
    baseline = ctl.chip_metrics()
    baseline_thr = baseline["chip_throughput"] if baseline else 0.0

    # -- phase 2: the storm, interleaved with continuing churn -----------
    # The generator's picks are uniform over the mesh; on a sparsely
    # loaded chip most would miss every resident, so each pick is mapped
    # onto the CURRENTLY-BOUND tiles / resident apps at injection time
    # (deterministic — the storm supplies the randomness, occupancy the
    # targets; a production chip at load faults under its tenants too).
    storm = failure_storm(
        n_faults, hw.n_tiles, seed=seed + 2,
        drift_apps=names, **storm_kw,
    )
    side = mesh[1]

    def _bound_tiles() -> list[int]:
        return sorted({
            int(t) for ts in ctl.running().values() for t in ts
        })

    def _target_link(a: int, horiz: bool) -> tuple[int, int]:
        bound = _bound_tiles()
        base = bound[a % len(bound)] if bound else a
        if horiz:
            nb = base + 1 if base % side + 1 < side else base - 1
        else:
            nb = base + side if base + side < hw.n_tiles else base - side
        return (min(base, nb), max(base, nb))
    rows = [(
        "event", "kind", "detail", "residents", "recovery_s",
        "displaced", "stale", "seed_throughput", "chip_throughput",
        "dead_tiles",
    )]
    recoveries: list[float] = []
    displaced_total = 0
    dead_binding_violations = 0
    heal_map: dict[tuple, tuple] = {}
    link_map: dict[tuple, tuple] = {}
    storm_t0 = time.perf_counter()
    for i, ev in enumerate(storm):
        for _ in range(churn_per_fault):
            _churn_step(ctl, rng, names, probs, requests)
        n_before = len(ctl.events)
        t_ev = time.perf_counter()
        if ev.kind == "fail":
            bound = [t for t in _bound_tiles() if not ctl.chip.dead[t]]
            tiles = tuple(sorted(
                {bound[t % len(bound)] for t in ev.tiles} if bound
                else {t for t in ev.tiles if not ctl.chip.dead[t]}
            ))
            heal_map[ev.tiles] = tiles
            if not tiles:
                continue
            disp = ctl.inject_fault(list(tiles))
        elif ev.kind == "heal" and ev.link is not None:
            link = link_map.pop(ev.link, None)
            if link is None or link not in ctl.chip.link_throttle:
                continue
            ev = dataclasses.replace(ev, link=link)
            disp = ctl.heal(links=[link])
        elif ev.kind == "heal":
            tiles = tuple(
                t for t in heal_map.pop(ev.tiles, ev.tiles)
                if ctl.chip.dead[t]
            )
            if not tiles:
                continue
            disp = ctl.heal(list(tiles))
        elif ev.kind == "throttle":
            a, b = ev.link
            link = _target_link(a, horiz=(b - a == 1))
            link_map[ev.link] = link
            ev = dataclasses.replace(ev, link=link)
            disp = ctl.inject_fault(links=[link], throttle=ev.factor)
        else:   # drift
            app = ev.app
            if app not in ctl.state.allocated:
                res = sorted(ctl.state.allocated)
                if not res:
                    continue
                app = res[i % len(res)]
                ev = dataclasses.replace(ev, app=app)
            disp = ctl.inject_drift(app, ev.factor)
        wall = time.perf_counter() - t_ev
        if ev.kind == "fail":
            recoveries.append(wall)
        displaced_total += len(disp)
        dead_binding_violations += _dead_binding_violations(ctl)
        new = ctl.events[n_before:]
        remaps = [e for e in new if e.kind == "remap"]
        detail = (
            f"link={ev.link}x{ev.factor:.2f}" if ev.link is not None
            else f"tiles={list(tiles)}" if ev.kind in ("fail", "heal")
            else f"{ev.app}x{ev.factor:.2f}"
        )
        rows.append((
            i, ev.kind, detail, len(ctl.state.allocated), round(wall, 4),
            len(disp),
            sum(len(e.app_throughputs) for e in remaps),
            round(remaps[-1].seed_throughput, 6) if remaps else 0.0,
            round(remaps[-1].chip_throughput, 6) if remaps else 0.0,
            int(ctl.chip.dead.sum()),
        ))
    storm_s = time.perf_counter() - storm_t0

    # -- never-regress: every remap vs. its repaired seed ----------------
    remap_events = [e for e in ctl.events if e.kind == "remap"]
    regressions = sum(
        1 for e in remap_events
        if e.seed_throughput > 0
        and e.chip_throughput < e.seed_throughput * (1 - 1e-6)
    )
    never_regressed = regressions == 0

    # -- retention checkpoints: full re-opt under the SAME degraded chip -
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
            0, 0, 0.0,
            round(after["chip_throughput"], 6) if after else 0.0,
            int(ctl.chip.dead.sum()),
        ))

    p50, p99 = _percentiles(recoveries)
    retention_min = min(retention, default=1.0)
    n_recovery_events = len(remap_events)

    # smoke runs a deliberately congested 8x8 where retention measures
    # churn packing rather than fault recovery; the perf gates (p99,
    # retention) bind only on the full 32x32 scenario.
    ok = (
        n_recovery_events > 0
        and never_regressed
        and dead_binding_violations == 0
        and (smoke or (p99 < 1.0 and retention_min >= 0.9))
    )
    summary = {
        "mesh": list(mesh),
        "n_tiles": hw.n_tiles,
        "n_tenants": n_tenants,
        "n_warmup": n_warmup,
        "n_faults": len(storm),
        "storm_kinds": {
            k: sum(1 for e in storm if e.kind == k)
            for k in ("fail", "heal", "throttle", "drift")
        },
        "tenant_scale": scale,
        "joint_budget": list(joint_budget),
        "design_wall_s": round(design_wall_s, 2),
        "warmup_s": round(warmup_s, 2),
        "storm_s": round(storm_s, 2),
        "baseline_throughput": round(baseline_thr, 6),
        "residents_at_storm_end": len(ctl.state.allocated),
        "dead_tiles_at_end": int(ctl.chip.dead.sum()),
        "recovery_events": n_recovery_events,
        "displaced": displaced_total,
        "recovery_p50_s": round(p50, 4),
        "recovery_p99_s": round(p99, 4),
        "never_regressed": never_regressed,
        "regressions": regressions,
        "dead_binding_violations": dead_binding_violations,
        "retention_vs_full": [round(r, 4) for r in retention],
        "retention_min": round(retention_min, 4),
        "ok": ok,
    }
    return rows, summary, ok


def run(out_path: str = "BENCH_faults.json", *, smoke: bool = False,
        **kw):
    rows, summary, ok = faults_bench(smoke=smoke, **kw)
    from .common import write_bench
    write_bench(out_path, {"faults_bench": summary})
    return rows, summary, ok


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_faults.json")
    ap.add_argument("--smoke", action="store_true",
                    help="10 tenants / 4 faults on an 8x8 mesh (CI tier-1)")
    ap.add_argument("--tenants", type=int, default=96)
    ap.add_argument("--warmup", type=int, default=160)
    ap.add_argument("--faults", type=int, default=30)
    ap.add_argument("--scale", type=float, default=0.06)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rows, summary, ok = run(
        args.out, smoke=args.smoke, n_tenants=args.tenants,
        n_warmup=args.warmup, n_faults=args.faults, scale=args.scale,
        seed=args.seed,
    )
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", json.dumps(summary))
    print("OK" if ok else "FAILED")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
