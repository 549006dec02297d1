#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, through the entry points a user calls.

  python chip_smoke.py              # phases (a)-(d) on one chip

(a) Device check: the first jax device must be a TPU; the Pallas (max,+)
    kernels and a small exact solve must match their oracles on it.
(b) Full-size designs: the eight Table-1 apps at published size are
    registered (design-time compile) and admitted on the 1024-tile chip
    by one joint-placement ``AdmissionController``.
(c) Serving burst: the full ``benchmarks.serving`` burst (224 tenants,
    640 Zipf-1.1 admit/evict events, window 16, joint budget (1, 6),
    seed 0) drained through ``ServingQueue.drain()``; it must drain and
    never regress.  Every exact solve goes through ``"auto"``.
(d) Reference check: the final chip of (b) and of (c) is rescored with
    the numpy ``"edges"`` oracle and with ``"csr-jit"`` (max relative
    error <= 1e-6), the cached chip metrics must match the exact
    full-union ones, and every device solve must have run on the TPU.

Everything is generated from seeds.  Wall times include compilation:
this is a smoke run, not a benchmark.  The last line of stdout is
``{"ok": true, "device": {...}}`` only when every phase passed; any
failure exits nonzero without it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

#: max relative error of "csr-jit" against the numpy "edges" oracle
#: (the ``rel_err_bar`` of ``benchmarks.maxplus_backends``)
REL_ERR_BAR = 1e-6
#: benchmarks.serving's full burst configuration
BURST = dict(
    smoke=False, n_tenants=224, n_events=640, scale=0.06,
    joint_budget=(1, 6), seed=0,
)
WINDOW = 16
#: tiles each Table-1 app asks for in (b): eight apps share 1024 tiles
APP_TILES = 128


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


class DispatchLog:
    """Devices of every exact device solve, one entry per solve call.

    Wraps the ``repro.kernels.maxplus_bellman`` entry points the analysis
    layer calls: each ``mcr_bisect_device`` call opens an entry, and its
    dispatch appends the device its result arrays live on.
    """

    def __init__(self):
        from repro.kernels import maxplus_bellman as kbell

        self.calls: list[list] = []
        dispatch = kbell._dispatch_bisect
        solve = kbell.mcr_bisect_device

        def recording_dispatch(*args, **kw):
            out = dispatch(*args, **kw)
            self.calls[-1].extend(out[0].devices())
            return out

        def opening(*args, **kw):
            self.calls.append([])
            return solve(*args, **kw)

        kbell._dispatch_bisect = recording_dispatch
        kbell.mcr_bisect_device = opening

    def devices(self) -> list:
        return [d for devs in self.calls for d in devs]


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def phase_device() -> dict:
    import jax

    from repro.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    devs = jax.devices()
    d0 = devs[0]
    print(f"jax {jax.__version__}", flush=True)
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)
    check(d0.platform == "tpu", f"first jax device is {d0.platform}, not tpu")
    check_semiring_kernels()
    check_small_solve()
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def phase_designs():
    from repro.core import APP_NAMES, DYNAP_SE_1024, AdmissionController
    from repro.core import build_app

    ctl = AdmissionController(DYNAP_SE_1024, placement="joint")
    print("app,neurons,n_clusters,tiles,register_s,admit_s", flush=True)
    for name in APP_NAMES:
        snn = build_app(name)
        t0 = time.perf_counter()
        art = ctl.register(snn)
        t_reg = time.perf_counter() - t0
        tiles = min(APP_TILES, art.clustered.n_clusters)
        t0 = time.perf_counter()
        ctl.admit(name, n_tiles_request=tiles)
        t_adm = time.perf_counter() - t0
        print(f"{name},{snn.n_neurons},{art.clustered.n_clusters},{tiles},"
              f"{t_reg:.3f},{t_adm:.3f}", flush=True)
    check(sorted(ctl.state.allocated) == sorted(APP_NAMES),
          "all eight Table-1 apps resident")
    return ctl


def drain_burst():
    """(controller, burst result) of the full burst on a fresh controller."""
    from benchmarks.serving import build_workload, make_controller, run_burst

    hw, _, stream, requests, design_ctl, _, _ = build_workload(**BURST)
    ctl = make_controller(hw, BURST["joint_budget"])
    ctl.artifacts = design_ctl.artifacts
    burst = run_burst(ctl, stream, requests, coalesce_window=WINDOW)
    return ctl, burst


def phase_burst(log: DispatchLog):
    n0 = len(log.calls)
    ctl, burst = drain_burst()
    s = burst["service"]
    print(f"burst: events={burst['events']} admitted={s['admitted']} "
          f"evicted={s['evicted']} rejected={s['rejected']} "
          f"flushes={s['flushes']} drain_s={burst['event_loop_s']} "
          f"admissions_per_s={burst['admissions_per_s']}", flush=True)
    check(burst["drained"], "burst drained")
    check(burst["never_regressed"], "burst never regressed")
    check(len(log.calls) > n0, "the burst's 'auto' scoring ran device solves")
    return ctl


def rescore_max_rel_err(ctl) -> float:
    """Rescore the resident union (one row) with "edges" and "csr-jit"."""
    import numpy as np

    from repro.core import batch_execute, project_order_batch

    _, _, union, order, binding, _ = ctl._resident_union()
    ob = project_order_batch(order, binding[None, :])
    pe, pc = (
        batch_execute(union, binding, ctl.hw, ob, backend=b,
                      chip_state=ctl.chip).periods
        for b in ("edges", "csr-jit")
    )
    check(bool(np.isfinite(pe).all()), f"finite edges periods {pe}")
    check(np.array_equal(np.isfinite(pc), np.isfinite(pe)),
          f"csr-jit {pc} vs edges {pe}")
    return float(np.max(np.abs(pc - pe) / np.abs(pe)))


def check_semiring_kernels() -> None:
    """The Pallas (max,+) kernels, compiled for the chip, against the jnp
    oracles of ``repro.kernels.ref`` on seeded inputs."""
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    a = rng.normal(size=(4, 256, 384)).astype(np.float32)
    b = rng.normal(size=(4, 384, 128)).astype(np.float32)
    x = rng.normal(size=(4, 384)).astype(np.float32)
    pairs = {
        "maxplus_matmul": (ops.maxplus_matmul(a[0], b[0]),
                           ref.maxplus_matmul_ref(a[0], b[0])),
        "maxplus_bmm": (ops.maxplus_bmm(a, b), ref.maxplus_bmm_ref(a, b)),
        "maxplus_bmv": (ops.maxplus_bmv(a, x), ref.maxplus_bmv_ref(a, x)),
    }
    for name, (got, want) in pairs.items():
        err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
        print(f"kernel {name}: max_abs_err_vs_ref={err!r}", flush=True)
        check(err <= 1e-5, f"{name} matches its oracle")


def check_small_solve() -> None:
    """A seeded 8-row stack through "csr-jit" and the "edges" oracle: the
    float64 bisection on the chip before any phase depends on it."""
    import numpy as np

    from repro.core import mcr_batch
    from repro.core.maxplus import EdgeStack

    rng = np.random.default_rng(0)
    b, n, e = 8, 6, 18
    src = rng.integers(0, n, size=(b, e))
    dst = rng.integers(0, n, size=(b, e))
    tok = rng.integers(0, 3, size=(b, e))
    src[:, 0] = dst[:, 0] = 0
    tok[:, 0] = 1                      # a token-carrying self loop: live
    stack = EdgeStack(n_actors=n, src=src, dst=dst, tokens=tok,
                      weights=rng.uniform(0.1, 5.0, size=(b, e)))
    pe, pc = (mcr_batch(stack, backend=k) for k in ("edges", "csr-jit"))
    err = float(np.max(np.abs(pc - pe) / np.abs(pe)))
    print(f"small solve: max_rel_err_vs_edges={err!r}", flush=True)
    check(err <= REL_ERR_BAR, "small csr-jit solve matches edges")


def phase_reference(chips: dict, log: DispatchLog) -> None:
    import jax

    from repro.core import engine

    check(engine._resolve_backend("auto") == "csr-jit",
          "'auto' resolves to csr-jit")
    worst = 0.0
    for label, ctl in chips.items():
        err = rescore_max_rel_err(ctl)
        cached = ctl.chip_metrics()
        exact = ctl.chip_metrics(exact=True)
        dp = abs(cached["chip_period"] - exact["chip_period"]) \
            / exact["chip_period"]
        de = abs(cached["chip_energy"] - exact["chip_energy"]) \
            / exact["chip_energy"]
        print(f"reference[{label}]: residents={exact['n_resident']} "
              f"max_rel_err_vs_edges={err!r} cached_vs_exact_period="
              f"{dp!r} cached_vs_exact_energy={de!r}", flush=True)
        check(err <= REL_ERR_BAR, f"{label}: rel err {err} > {REL_ERR_BAR}")
        check(cached["n_resident"] == exact["n_resident"]
              and dp <= REL_ERR_BAR and de <= REL_ERR_BAR,
              f"{label}: cached chip metrics match exact")
        worst = max(worst, err)
    devs = log.devices()
    on = Counter(str(d) for d in devs)
    print(f"device solves: {len(log.calls)} calls on {dict(on)}", flush=True)
    check(bool(devs) and all(d.platform == "tpu" for d in devs),
          "every device solve ran on the TPU")
    check(set(devs) <= set(jax.devices()), "solves on visible devices")
    print(f"max_rel_err_vs_edges={worst!r}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.parse_args()

    from jax import monitoring

    cache = Counter()

    def count_cache_event(event, **_):
        if event.startswith("/jax/compilation_cache/"):
            cache[event.rsplit("/", 1)[-1]] += 1

    monitoring.register_event_listener(count_cache_event)
    t_all = time.perf_counter()
    device = phase_device()
    log = DispatchLog()
    chips = {}
    for phase, fn in (
        ("designs", phase_designs),
        ("burst", lambda: phase_burst(log)),
    ):
        t0 = time.perf_counter()
        chips[phase] = fn()
        print(f"phase {phase}: {time.perf_counter() - t0:.3f} s", flush=True)
    t0 = time.perf_counter()
    phase_reference(chips, log)
    print(f"phase reference: {time.perf_counter() - t0:.3f} s", flush=True)
    print(f"compile cache events: {dict(cache)}", flush=True)
    print(f"total: {time.perf_counter() - t_all:.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
