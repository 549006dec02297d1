"""The comparison that decides ``correct``, and its float32 control.

Numbers compared, each against the limit in the mix's ``check`` block:

``solve_gap``
    Device solve layer.  For a sample of the window's exact solves (drawn
    from the seed, the largest always in it) and a sample of each one's
    rows (the best candidate always in it): the largest
    ``|period - reference| / (reference * rel_tol)``, the gap in units of
    the tolerance the caller asked the solve for.
``app_period_gap``
    Engine stack build and controller state.  For every resident after
    the window: the largest relative gap between the period the controller
    reports for it and the period the reference rebuilds from the seeded
    network and the program's clustering, tiles and firing orders.
``ownership_errors``
    Controller state: residents that the answered requests do not imply,
    clusters bound outside their tenant's tiles, tiles outside the chip,
    and (isolated placement) tiles held twice or a tile count other than
    the one asked for.
``solves_off_device``
    Exact solves of the window whose results did not live on the
    accelerator the run measures.  A run in which no solve reached a
    device at all is not correct either.

The control puts the reference, computed in float32, in the program's
place for the first two numbers.
"""

from __future__ import annotations

import math

import numpy as np

from . import reference


def _gap(prog, ref, scale):
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    both = np.isfinite(prog) & np.isfinite(ref)
    if not np.array_equal(np.isfinite(prog), np.isfinite(ref)) or \
            np.any(~both & (prog != ref)):
        return math.inf
    if not both.any():
        return 0.0
    return float(np.max(np.abs(prog[both] - ref[both])
                        / (np.abs(ref[both]) * scale)))


def _rows(rec, n_rows, rng):
    periods = rec["periods"]
    b = periods.size
    if b <= n_rows:
        return list(range(b))
    finite = np.where(np.isfinite(periods), periods, np.inf)
    best = int(np.argmin(finite))
    pick = set(rng.choice(b, size=n_rows, replace=False).tolist())
    return sorted(pick | {best})


def _stack(rec):
    s = rec["stack"]
    return {"n_actors": s.n_actors, "src": s.src, "dst": s.dst,
            "tokens": s.tokens, "weights": s.weights}


def solve_gap(sample, n_rows, seed, *, control=False):
    rng = np.random.default_rng([int(seed) & (2**64 - 1), 7])
    worst = 0.0
    for rec in sample:
        rows = _rows(rec, n_rows, rng)
        ref = reference.stack_ratios(_stack(rec), rows)
        got = (reference.stack_ratios(_stack(rec), rows, dtype=np.float32)
               if control else rec["periods"][rows])
        worst = max(worst, _gap(got, ref, rec["rel_tol"]))
    return worst


def resident_apps(dep) -> list:
    ctl = dep.ctl
    apps = []
    for name in sorted(ctl.state.allocated):
        art = ctl.artifacts[(name, ctl.hw)]
        c = art.clustered
        apps.append({
            "name": name,
            "snn": {"pre": c.snn.pre, "post": c.snn.post,
                    "spikes": c.snn.spikes, "layer_of": c.snn.layer_of},
            "cluster_of": c.cluster_of,
            "binding": ctl.reports[name].binding,
            "order": art.single_order,
        })
    return apps


def reported_periods(dep) -> dict:
    ctl = dep.ctl
    if ctl.track_chip_metrics:
        m = ctl.chip_metrics()
        thr = m["app_throughputs"] if m else {}
    else:
        thr = {n: r.throughput for n, r in ctl.reports.items()}
    return {n: (1.0 / t if t > 0 else math.inf) for n, t in thr.items()}


def app_period_gap(dep, *, control=False):
    apps = resident_apps(dep)
    if not apps:
        return 0.0
    hw = dep.config["hardware"]
    ref = reference.app_periods(apps, hw)
    got = (reference.app_periods(apps, hw, dtype=np.float32) if control
           else reported_periods(dep))
    names = sorted(ref)
    if sorted(got) != names:
        return math.inf
    return _gap([got[n] for n in names], [ref[n] for n in names], 1.0)


def ownership_errors(dep, expected: set) -> int:
    ctl = dep.ctl
    alloc = ctl.state.allocated
    errors = len(set(alloc) ^ set(expected))
    held: dict = {}
    for name, tiles in alloc.items():
        tiles = set(int(t) for t in tiles)
        rep = ctl.reports.get(name)
        if rep is None or not set(rep.binding.tolist()) <= tiles:
            errors += 1
        errors += sum(1 for t in tiles if not 0 <= t < dep.hw.n_tiles)
        if ctl.placement == "isolated":
            errors += len(tiles) != dep.requests[name]
            for t in tiles:
                errors += t in held
                held[t] = name
    return errors


def solves_off_device(probe, platform: str) -> int:
    return sum(1 for c in probe.window_calls()
               if any(d.platform != platform for d in c["devices"]))


def compare(dep, probe, expected, *, seed, check: dict, platform: str,
            control: bool = False) -> tuple[dict, dict]:
    """(numbers, control numbers); control numbers empty unless asked."""
    sample = probe.sample()
    numbers = {
        "solve_gap": solve_gap(sample, check["rows"], seed),
        "app_period_gap": app_period_gap(dep),
        "ownership_errors": ownership_errors(dep, expected),
        "solves_off_device": solves_off_device(probe, platform),
    }
    ctrl = {}
    if control:
        ctrl = {
            "solve_gap": solve_gap(sample, check["rows"], seed, control=True),
            "app_period_gap": app_period_gap(dep, control=True),
        }
    return numbers, ctrl
