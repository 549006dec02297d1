#!/usr/bin/env python3
"""Runs one benchmark cell once on the accelerator and prints its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout, builds the cell's
deployment, warms up, measures for ``--seconds``, checks what the window
produced against the plain reference, and prints one JSON line as the last
line of standard output.  With ``--trace 0`` its metrics are the cell's
end-to-end metrics; with ``--trace 1`` the window is traced with the JAX
profiler and the metrics are the cell's per-layer metrics.  It exits
nonzero, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.

Everything is found by name, so new work is new files plus entries:

* a cell is an entry of ``workloads`` in ``BENCHMARK.json`` that names a
  configuration and a traffic mix;
* a configuration is ``bench/configs/<config>.json`` (chip, tenants,
  controller options; see ``bench/deploy.py``); its ``tenants`` block
  names the builder ``bench/configs/<builder>.py`` that makes the
  networks;
* a traffic mix is ``bench/traffic/<traffic>.json``: the parameters of the
  loop ``bench/traffic/<loop>.py`` that it names (see ``bench/loops.py``),
  and the sample sizes and limits of its ``check`` block (see
  ``bench/check.py``);
* a metric is an entry of ``end_to_end`` or ``per_layer`` and a reader
  ``bench/metrics/<base>.py``, where ``<base>`` is the metric's name up to
  its first dot; ``read(run)`` returns a number, or None when there is
  nothing to read.

``--control 1`` puts the float32 control in the program's place in the
comparison, so that ``correct`` has to come out false; the benchmark's own
runs never pass it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import check, deploy, loops, probes, trace  # noqa: E402

OUT_DIR = ROOT / ".bench_out"
#: a traced run traces the last this many seconds of its window: the
#: profiler's stop and the reading of its trace grow with the events, and
#: a whole 51 s window holds some 2.5 million device ops
TRACE_TAIL_S = 15.0


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunRecord:
    """What a metric reader sees of one run."""

    window: loops.Window
    setup_s: float
    spans: object = None            # probes.Spans in a traced run
    trace: dict | None = None       # trace.reduce() of a traced run
    compiles_in_window: int = 0


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix) of one cell."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    mix = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, mix


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, run: RunRecord):
    reader = importlib.import_module(f"bench.metrics.{name.split('.')[0]}")
    return reader.read(run)


def accelerator(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"need {chips} TPU accelerator chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs


def run_cell(bench, cell, config, mix, *, seed, seconds, traced=False,
             control=False, require_accelerator=True, t_start=T_START,
             log=print):
    """One run of one cell; returns the result line as a dict."""
    import jax

    if require_accelerator:
        devs = accelerator(cell["chips"])
    else:
        devs = jax.devices()
    from repro.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache_dir}")
    platform = devs[0].platform

    dep = deploy.build(config)
    log(f"registered {len(dep.tenants)} tenants")
    loop = loops.make(dep, mix, seed, seconds)
    probe = probes.SolveProbe(keep=mix["check"]["solves"], seed=seed)
    compiles = probes.CompileCounter()
    spans = None
    trace_dir = OUT_DIR / "trace" / f"{cell['name']}-{seed}"
    try:
        loop.warmup()
        for app, sec in getattr(loop, "warmup_s", []):
            log(f"warm-up request {app}: {sec:.3f} s")
        setup_compiles = compiles.count(0.0, time.perf_counter())
        if traced:
            spans = probes.Spans(dep.ctl, annotate=True)
            shutil.rmtree(trace_dir, ignore_errors=True)
        setup_s = time.perf_counter() - t_start
        probe.in_window = True
        tracing = []
        if traced:
            def start_tracing(release):
                if not tracing and release >= seconds - TRACE_TAIL_S:
                    # host spans come from the harness's annotations; the
                    # Python tracer would add an event per Python call
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(str(trace_dir),
                                             profiler_options=opts)
                    tracing.append(jax.profiler.TraceAnnotation(trace.WINDOW))
                    tracing[0].__enter__()
            loop.on_release = start_tracing
        window = loop.run(seconds)
        probe.in_window = False
        if tracing:
            tracing[0].__exit__(None, None, None)
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace of the last {TRACE_TAIL_S} s stopped in "
                f"{time.perf_counter() - t0:.3f} s")
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs[: cell["chips"]])
        n_compiles, n_hits = compiles.count(window.t_open, window.t_end)
        reduced = None
        log(f"setup {setup_s:.3f} s, window {seconds} s")
        if traced:
            t0 = time.perf_counter()
            events = trace.load(str(trace_dir))
            log(f"trace lines: {trace.lines(events)}")
            reduced = trace.reduce(events)
            log(f"trace read and reduced in {time.perf_counter() - t0:.3f} s")
            shutil.rmtree(trace_dir, ignore_errors=True)
    finally:
        for p in (spans, compiles, probe):
            if p is not None:
                p.close()

    run = RunRecord(window=window, setup_s=setup_s, spans=spans,
                    trace=reduced, compiles_in_window=n_compiles)
    due = window.due_in_window()
    failed = sum(1 for r in due if r.status not in ("ok", "skipped"))
    calls = probe.window_calls()
    log(f"set-up: {setup_compiles[0]} XLA compiles, {setup_compiles[1]} "
        f"compile-cache hits")
    log(f"window: {len(due)} requests due, {failed} failed, "
        f"{len(window.requests)} answered, open {seconds} s, "
        f"answered by {window.t_end - window.t_open:.3f} s")
    log(f"window: {len(calls)} device solves, largest "
        f"{max((c['rows'] * c['n'] for c in calls), default=0)} rows x "
        f"nodes, {n_compiles} XLA compiles, {n_hits} compile-cache hits")
    for r in window.requests:
        log(f"request {r.kind} {r.app}: {r.done - r.start:.3f} s {r.status}")
    if getattr(loop, "round_s", None):
        log(f"window: rounds of {[round(x, 3) for x in loop.round_s]} s")
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, cell["name"], kind):
        value = read_metric(m["name"], run)
        if value is None:
            continue
        if not math.isfinite(value):
            raise RuntimeError(f"metric {m['name']} is {value}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    t0 = time.perf_counter()
    numbers, ctrl = check.compare(
        dep, probe, loop.expected_residents(), seed=seed,
        check=mix["check"], platform=platform, control=control)
    log(f"reference: {time.perf_counter() - t0:.3f} s over "
        f"{len(probe.sample())} sampled solves and "
        f"{len(dep.ctl.state.allocated)} residents")
    limits = mix["check"]["limits"]
    if control:
        for k, v in numbers.items():
            log(f"program's own {k} = {v!r} (limit {limits[k]!r})")
        numbers = {**numbers, **ctrl}
    # JSON has no infinity: a gap that is infinite prints as the largest float
    checks = {k: {"value": v if math.isfinite(v) else sys.float_info.max,
                  "limit": limits[k]} for k, v in numbers.items()}
    on_device = [c for c in calls if c["devices"]]
    correct = bool(on_device) and all(
        v <= limits[k] for k, v in numbers.items())
    if not on_device:
        log("no exact solve reached a device in the window")

    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        log(f"trace: programs {reduced['program_s']}")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the float32 control in the program's "
                         "place (not a benchmark run)")
    args = ap.parse_args(argv)
    bench, cell, config, mix = load_cell(args.workload)
    err = lambda msg: print(msg, file=sys.stderr, flush=True)
    try:
        result = run_cell(
            bench, cell, config, mix, seed=args.seed, seconds=args.seconds,
            traced=bool(args.trace), control=bool(args.control), log=err)
    except NoAccelerator as e:
        err(f"error: {e}")
        return 2
    for k, c in result["checks"].items():
        err(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
