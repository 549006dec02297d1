"""Max-plus backend sweep: "edges" vs "csr-jit" vs "dense" (ISSUE 9).

  PYTHONPATH=src python -m benchmarks.maxplus_backends           # full sweep
  PYTHONPATH=src python -m benchmarks.maxplus_backends --smoke   # CI tier-1
  PYTHONPATH=src python -m benchmarks.run maxplus                # via runner

Times :func:`repro.core.maxplus.mcr_batch` across (B, n, E) stack shapes
and backends and cross-validates every backend against the numpy
``"edges"`` float64 oracle.  Two graph families:

  * **shortcut** — one-token rings carrying the PR-3 path-doubling
    shortcut edges plus random chords: the shape
    :func:`~repro.core.engine.stack_hardware_aware` actually emits with
    ``relax_shortcuts=True`` (hop diameter O(log n)).  This is the
    headline: the acceptance bar is ``"csr-jit"`` >= 3x faster than
    ``"edges"`` at B >= 64, n >= 256 with <= 1e-6 relative error.
  * **ring** — the same rings WITHOUT shortcuts: hop diameter n-1, the
    documented worst case for the blocked device sweep (each Bellman-
    Ford probe needs ~n rounds and the early-exit check can't save
    them), kept honest in the output rather than hidden.

The dense float32 squaring backend is probed at one small shape only
(Pallas interpret mode makes it minutes-slow at n >= 64 on CPU hosts)
together with its per-bisection squaring-round counts — evidence that
the shortcut-derived fixpoint exit (satellite a) beats the log2(n) cap.

``followups.shape_bucket_padding`` measures satellite (c): total
``"csr-jit"`` wall time over a burst of slightly-varying batch sizes
with and without :func:`~repro.core.engine.pad_stack_to_buckets` —
bucketing stabilizes the jitted program's (B*n, d_max) signature, so
padding wins whenever shapes churn (the engine's default
``pad_shapes=True`` for device backends).

Writes ``BENCH_maxplus.json`` (schema in README.md).
"""

from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np

from repro.compile_cache import configure_compile_cache
from repro.core import maxplus as mp
from repro.core.engine import pad_stack_to_buckets
from repro.core.maxplus import EdgeStack, mcr_batch

REL_ERR_BAR = 1e-6
SPEEDUP_BAR = 3.0


def make_stack(
    b: int, n: int, seed: int, *, shortcuts: bool, chords: int = 8
) -> EdgeStack:
    """One-token rings (+ random chords) with optional exact path-doubling
    shortcut edges — the synthetic twin of the engine's
    ``relax_shortcuts=True`` hardware-aware stacks."""
    r = np.random.default_rng(seed)
    src = np.broadcast_to(np.arange(n), (b, n)).copy()
    dst = (src + 1) % n
    tok = np.zeros_like(src)
    tok[:, -1] = 1
    w = r.uniform(0.5, 2.0, (b, n))
    srcs, dsts, toks, ws = [src], [dst], [tok.astype(np.float64)], [w]
    if shortcuts:
        cw, ct, nx = w.copy(), tok.astype(np.float64), dst.copy()
        span = 1
        while 2 * span < n:
            cw = cw + np.take_along_axis(cw, nx, axis=1)
            ct = ct + np.take_along_axis(ct, nx, axis=1)
            nx = np.take_along_axis(nx, nx, axis=1)
            span *= 2
            srcs.append(src)
            dsts.append(nx.copy())
            toks.append(ct.copy())
            ws.append(cw.copy())
    if chords:
        cs = r.integers(0, n, (b, chords))
        cd = r.integers(0, n, (b, chords))
        srcs.append(cs)
        dsts.append(cd)
        toks.append(np.ones((b, chords)))
        ws.append(r.uniform(0.1, 1.0, (b, chords)))
    return EdgeStack(
        n_actors=n,
        src=np.concatenate(srcs, axis=1),
        dst=np.concatenate(dsts, axis=1),
        tokens=np.concatenate(toks, axis=1).astype(np.int64),
        weights=np.concatenate(ws, axis=1),
    )


def _best_of(fn, repeats: int) -> tuple[float, np.ndarray]:
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Max relative period error; non-finite rows must match exactly."""
    if not np.array_equal(np.isfinite(got), np.isfinite(ref)):
        return float("inf")
    fin = np.isfinite(ref)
    if not fin.any():
        return 0.0
    return float(
        (np.abs(got[fin] - ref[fin]) / np.maximum(1.0, np.abs(ref[fin])))
        .max()
    )


def _sweep_point(b: int, n: int, family: str, seed: int,
                 repeats: int) -> dict:
    stack = make_stack(b, n, seed, shortcuts=(family == "shortcut"))
    t_edges, ref = _best_of(
        lambda: mcr_batch(stack, backend="edges", rel_tol=1e-9), repeats
    )
    mcr_batch(stack, backend="csr-jit", rel_tol=1e-9)     # jit warmup
    t_csr, got = _best_of(
        lambda: mcr_batch(stack, backend="csr-jit", rel_tol=1e-9), repeats
    )
    return {
        "family": family,
        "B": b,
        "n": n,
        "E": stack.n_edges,
        "edges_s": round(t_edges, 4),
        "csr_jit_s": round(t_csr, 4),
        "speedup_csr_vs_edges": round(t_edges / t_csr, 3) if t_csr else 0.0,
        "max_rel_err": _rel_err(got, ref),
    }


def _dense_probe(b: int, n: int, seed: int) -> dict:
    """Small-shape dense probe: agreement + realized squaring rounds."""
    short = make_stack(b, n, seed, shortcuts=True, chords=0)
    plain = make_stack(b, n, seed, shortcuts=False, chords=0)
    cap = max(1, int(math.ceil(math.log2(max(n, 2)))))
    t0 = time.perf_counter()
    ref = mcr_batch(plain, backend="edges", rel_tol=1e-9)
    t_edges = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = mcr_batch(short, backend="dense", rel_tol=1e-4)
    t_dense = time.perf_counter() - t0
    rounds_short = list(mp._DENSE_LAST_ROUNDS)
    mcr_batch(plain, backend="dense", rel_tol=1e-4)
    rounds_plain = list(mp._DENSE_LAST_ROUNDS)
    return {
        "B": b,
        "n": n,
        "sq_round_cap": cap,
        "mean_rounds_shortcut": round(float(np.mean(rounds_short)), 2),
        "mean_rounds_plain": round(float(np.mean(rounds_plain)), 2),
        "edges_s": round(t_edges, 4),
        "dense_s": round(t_dense, 4),
        "max_rel_err": _rel_err(got, ref),
        "rounds_reduced": float(np.mean(rounds_short))
        < float(np.mean(rounds_plain)),
    }


def _padding_followup(n: int, batches: list[int], seed: int) -> dict:
    """Satellite (c): does shape-bucket padding pay on the csr path?

    A burst of admissions never repeats the exact batch size; without
    bucketing every distinct B retraces the jitted bisection program.
    """
    stacks = [
        make_stack(b, n, seed + i, shortcuts=True)
        for i, b in enumerate(batches)
    ]

    def _run(pad: bool) -> float:
        t0 = time.perf_counter()
        for s in stacks:
            if pad:
                s, _ = pad_stack_to_buckets(s, None)
            mcr_batch(s, backend="csr-jit", rel_tol=1e-9)
        return time.perf_counter() - t0

    # each variant warms its own traces, then a timed pass re-enters them
    _run(False)
    raw_s = _run(False)
    _run(True)
    padded_s = _run(True)
    return {
        "n": n,
        "batch_sizes": batches,
        "csr_jit_raw_s": round(raw_s, 4),
        "csr_jit_padded_s": round(padded_s, 4),
        "padding_wins": padded_s < raw_s,
        "engine_default": "pad_shapes=True for dense/csr-jit",
    }


def maxplus_bench(*, smoke: bool = False, seed: int = 0,
                  repeats: int = 3):
    """Run the sweep; returns ``(rows, summary, ok)``."""
    if smoke:
        points = [(8, 32, "shortcut"), (8, 32, "ring")]
        repeats = 1
    else:
        points = [
            (16, 64, "shortcut"),
            (64, 256, "shortcut"),
            (128, 256, "shortcut"),
            (64, 256, "ring"),
        ]

    sweep = [
        _sweep_point(b, n, family, seed, repeats)
        for b, n, family in points
    ]
    agreement_ok = all(p["max_rel_err"] <= REL_ERR_BAR for p in sweep)

    headline = [
        p for p in sweep
        if p["family"] == "shortcut" and p["B"] >= 64 and p["n"] >= 256
    ]
    speedup_ok = smoke or (
        bool(headline)
        and all(p["speedup_csr_vs_edges"] >= SPEEDUP_BAR for p in headline)
    )

    followups = {}
    if not smoke:
        followups["dense_shortcut_rounds"] = _dense_probe(8, 32, seed)
        followups["shape_bucket_padding"] = _padding_followup(
            128, [57, 61, 64, 59, 63, 58, 62, 60], seed
        )
        agreement_ok = agreement_ok and (
            followups["dense_shortcut_rounds"]["max_rel_err"] <= 5e-4
        )

    ok = agreement_ok and speedup_ok
    summary = {
        "rel_err_bar": REL_ERR_BAR,
        "speedup_bar": SPEEDUP_BAR,
        "sweep": sweep,
        "followups": followups,
        "agreement_ok": agreement_ok,
        "speedup_ok": speedup_ok,
        "ok": ok,
    }
    rows = [("family", "B", "n", "E", "edges_s", "csr_jit_s",
             "speedup", "max_rel_err")]
    rows += [
        (p["family"], p["B"], p["n"], p["E"], p["edges_s"],
         p["csr_jit_s"], p["speedup_csr_vs_edges"],
         f"{p['max_rel_err']:.2e}")
        for p in sweep
    ]
    return rows, summary, ok


def run(out_path: str = "BENCH_maxplus.json", *, smoke: bool = False,
        **kw):
    rows, summary, ok = maxplus_bench(smoke=smoke, **kw)
    from .common import write_bench
    write_bench(out_path, {"maxplus_backends": summary})
    return rows, summary, ok


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_maxplus.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, agreement-only (CI tier-1)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    rows, summary, ok = run(
        args.out, smoke=args.smoke, seed=args.seed, repeats=args.repeats
    )
    for row in rows:
        print(",".join(str(x) for x in row))
    print("##", json.dumps(summary))
    print("OK" if ok else "FAILED")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
