"""Serving-loop benchmark: burst admission throughput vs per-event joint placement.

  PYTHONPATH=src python -m benchmarks.serving             # 32x32 full run
  PYTHONPATH=src python -m benchmarks.serving --smoke     # 12 tenants, 8x8
  PYTHONPATH=src python -m benchmarks.run serving         # via the runner

Replays the SAME Zipf-1.1 tenant churn as :mod:`benchmarks.stress`
(224 Table-1-fit tenants, 640 admit/evict events, 32x32 mesh) in two
modes against a joint-placement region-scoped
:class:`~repro.core.runtime.AdmissionController`:

  * **baseline** — every event runs its own region rebalance (the
    controller's normal per-event path, fused multi-component scoring
    included);
  * **burst** — all events submitted up front to a
    :class:`~repro.core.serving.ServingQueue` and drained with
    coalescing: one merged region rebalance per ``coalesce_window``
    applied events, scored through the fused cross-region path
    (:func:`~repro.core.optimize.optimize_binding_graphs_fused`).

Recorded into ``BENCH_serving.json`` (schema in README.md): sustained
admissions/s per mode, the per-rebalance never-regress check, flush/
coalescing counters, and the burst speedup over baseline.  Acceptance:
burst admissions/s beats the stored pre-refactor burst baseline
(10.716/s on the reference host) with ``never_regressed`` true.

A separate speculative pre-compilation bench (cold controller, the same churn
drained in waves through a :class:`~repro.core.serving.PrecompilePool`)
reports the cache-warm-hit-rate.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import (
    DYNAP_SE,
    DYNAP_SE_1024,
    AdmissionController,
    AdmissionError,
    PrecompilePool,
    ServingQueue,
)
from repro.core.workloads import workload_suite

from .stress import ZIPF_S, _tiles_request, _zipf_probs

#: pre-refactor burst throughput on the reference host (admissions/s);
#: the acceptance bar this benchmark must beat
STORED_BASELINE_ADMISSIONS_PER_S = 10.716


def _never_regressed(events) -> bool:
    """Each rebalance's chip throughput vs. the chip just before it."""
    ok, prev_thr = True, None
    for e in events:
        if e.chip_throughput and e.chip_throughput > 0:
            if (
                e.kind == "rebalance"
                and prev_thr is not None
                and prev_thr > 0
                and e.chip_throughput < prev_thr * (1 - 1e-6)
            ):
                ok = False
            prev_thr = e.chip_throughput
        elif e.kind in ("admit", "evict", "finish"):
            prev_thr = e.chip_throughput or None
    return ok


def make_controller(hw, joint_budget):
    return AdmissionController(
        hw,
        placement="joint",
        joint_budget=joint_budget,
        full_rebalance_every=0,
    )


def _event_stream(names, n_events, seed):
    """The deterministic Zipf churn (shared with benchmarks.stress)."""
    rng = np.random.default_rng(seed + 1)
    probs = _zipf_probs(len(names))
    return [names[int(rng.choice(len(names), p=probs))]
            for _ in range(n_events)]


def _run_baseline(ctl, stream, requests):
    """Per-event rebalancing: the stress-harness event loop."""
    admits = evicts = rejects = 0
    residents = []
    t0 = time.perf_counter()
    for name in stream:
        if name in ctl.state.allocated:
            ctl.evict(name)
            evicts += 1
        else:
            try:
                ctl.admit(name, n_tiles_request=requests[name])
                admits += 1
            except AdmissionError:
                rejects += 1
        residents.append(len(ctl.state.allocated))
    loop_s = time.perf_counter() - t0
    return {
        "events": len(stream),
        "admits": admits,
        "evicts": evicts,
        "rejects": rejects,
        "event_loop_s": round(loop_s, 2),
        "admissions_per_s": (
            round(admits / loop_s, 3) if loop_s > 0 else 0.0
        ),
        "never_regressed": _never_regressed(ctl.events),
        "max_residents": max(residents, default=0),
    }


def run_burst(ctl, stream, requests, *, coalesce_window):
    """Submit everything up front, drain with coalesced rebalances."""
    q = ServingQueue(ctl, coalesce_window=coalesce_window)
    submitted_admits = submitted_evicts = 0
    resident = set()
    for name in stream:
        # mirror the baseline's admit-if-absent / evict-if-resident
        # policy over the QUEUED (not yet applied) trajectory
        if name in resident:
            q.submit_evict(name)
            resident.discard(name)
            submitted_evicts += 1
        else:
            q.submit_admit(name, n_tiles_request=requests[name])
            resident.add(name)
            submitted_admits += 1
    t0 = time.perf_counter()
    service = q.drain()
    loop_s = time.perf_counter() - t0
    admits = service["admitted"]
    return {
        "events": len(stream),
        "submitted_admits": submitted_admits,
        "submitted_evicts": submitted_evicts,
        "coalesce_window": coalesce_window,
        "event_loop_s": round(loop_s, 2),
        "admissions_per_s": (
            round(admits / loop_s, 3) if loop_s > 0 else 0.0
        ),
        "drained": q.pending == 0,
        "never_regressed": _never_regressed(ctl.events),
        "max_residents": max(
            (len(ctl.state.allocated),), default=0
        ),
        "service": service,
    }


def build_workload(smoke, n_tenants, n_events, scale, joint_budget, seed):
    """Shared deterministic setup: hardware, tenants, churn, design cache."""
    if smoke:
        hw = dataclasses.replace(DYNAP_SE, n_tiles=64)
        n_tenants, n_events = 12, 36
    else:
        hw = DYNAP_SE_1024
    tenants = workload_suite(n_tenants, seed=seed, scale=scale)
    names = [s.name for s in tenants]
    stream = _event_stream(names, n_events, seed)
    requests = {}
    design_ctl = make_controller(hw, joint_budget)
    for snn in tenants:
        art = design_ctl.register(snn)
        requests[snn.name] = _tiles_request(art.clustered.n_clusters)
    return hw, tenants, stream, requests, design_ctl, n_tenants, n_events


def _precompile_bench(
    hw, tenants, stream, requests, *,
    joint_budget, coalesce_window, waves=4,
):
    """Speculative pre-compilation over a COLD controller.

    The same churn drained in ``waves`` batches: each drain first warms
    the :class:`PrecompilePool`'s frequency-decayed predictions (design
    artifacts + scoring shape buckets), so admissions of recurring
    tenants find their design work already done.  Reports the pool's
    hit/miss accounting — ``hit_rate`` is the cache-warm-hit-rate.
    """
    ctl = make_controller(hw, joint_budget)
    pool = PrecompilePool(
        ctl, source={s.name: s for s in tenants},
        top_k=max(4, len(tenants) // 8),
    )
    q = ServingQueue(ctl, coalesce_window=coalesce_window, precompile=pool)
    resident: set = set()
    per_wave = max(1, math.ceil(len(stream) / waves))
    t0 = time.perf_counter()
    for w in range(0, len(stream), per_wave):
        for name in stream[w:w + per_wave]:
            if name in resident:
                q.submit_evict(name)
                resident.discard(name)
            else:
                q.submit_admit(name, n_tiles_request=requests[name])
                resident.add(name)
        q.drain()
    loop_s = time.perf_counter() - t0
    return {
        "waves": int(math.ceil(len(stream) / per_wave)),
        "event_loop_s": round(loop_s, 2),
        "drained": q.pending == 0,
        **pool.stats(),
    }


def serving_bench(
    *,
    smoke: bool = False,
    n_tenants: int = 224,
    n_events: int = 640,
    scale: float = 0.06,
    joint_budget: tuple[int, int] = (1, 6),
    coalesce_window: int = 16,
    seed: int = 0,
):
    """Run both modes over the same churn; return ``(rows, payload, ok)``."""
    t0 = time.perf_counter()
    hw, tenants, stream, requests, design_ctl, n_tenants, n_events = (
        build_workload(smoke, n_tenants, n_events, scale, joint_budget, seed)
    )
    design_wall_s = time.perf_counter() - t0

    # baseline: fresh controller, per-event rebalancing
    base_ctl = make_controller(hw, joint_budget)
    base_ctl.artifacts = design_ctl.artifacts   # share the design cache
    baseline = _run_baseline(base_ctl, stream, requests)

    # burst: fresh controller, coalesced rebalancing
    burst_ctl = make_controller(hw, joint_budget)
    burst_ctl.artifacts = design_ctl.artifacts
    burst = run_burst(
        burst_ctl, stream, requests, coalesce_window=coalesce_window
    )

    # speculative pre-compilation: cold controller, wave-drained churn
    precompile = _precompile_bench(
        hw, tenants, stream, requests,
        joint_budget=joint_budget, coalesce_window=coalesce_window,
    )

    speedup = (
        burst["admissions_per_s"] / baseline["admissions_per_s"]
        if baseline["admissions_per_s"] > 0 else 0.0
    )
    beats_stored = (
        smoke
        or burst["admissions_per_s"] > STORED_BASELINE_ADMISSIONS_PER_S
    )
    ok = (
        baseline["never_regressed"]
        and burst["never_regressed"]
        and burst["drained"]
        and beats_stored
        and precompile["drained"]
    )
    summary = {
        "mesh": list(hw.mesh_shape),
        "n_tiles": hw.n_tiles,
        "n_tenants": n_tenants,
        "n_events": n_events,
        "tenant_scale": scale,
        "zipf_s": ZIPF_S,
        "joint_budget": list(joint_budget),
        "coalesce_window": coalesce_window,
        "design_wall_s": round(design_wall_s, 2),
        "baseline": baseline,
        "burst": burst,
        "precompile": precompile,
        "speedup_burst_vs_baseline": round(speedup, 3),
        "stored_baseline_admissions_per_s": STORED_BASELINE_ADMISSIONS_PER_S,
        "beats_stored_baseline": beats_stored,
        "ok": ok,
    }
    rows = [
        ("mode", "events", "admits", "event_loop_s", "admissions_per_s",
         "never_regressed"),
        ("baseline", n_events, baseline["admits"],
         baseline["event_loop_s"], baseline["admissions_per_s"],
         baseline["never_regressed"]),
        ("burst", n_events, burst["service"]["admitted"],
         burst["event_loop_s"], burst["admissions_per_s"],
         burst["never_regressed"]),
    ]
    return rows, summary, ok


def run(out_path: str = "BENCH_serving.json", *, smoke: bool = False,
        **kw):
    rows, summary, ok = serving_bench(smoke=smoke, **kw)
    from .common import write_bench
    write_bench(out_path, {"serving_bench": summary})
    return rows, summary, ok


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--smoke", action="store_true",
                    help="12 tenants on an 8x8 mesh (CI tier-1)")
    ap.add_argument("--tenants", type=int, default=224)
    ap.add_argument("--events", type=int, default=640)
    ap.add_argument("--scale", type=float, default=0.06)
    ap.add_argument("--window", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    rows, summary, ok = run(
        args.out, smoke=args.smoke, n_tenants=args.tenants,
        n_events=args.events, scale=args.scale,
        coalesce_window=args.window, seed=args.seed,
    )
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", json.dumps(summary))
    print("OK" if ok else "FAILED")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
