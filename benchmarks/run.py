"""Benchmark entry point: one function per paper table/figure + the LM
roofline table from dry-run artifacts.  Prints CSV blocks.

  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run fig13        # one benchmark
  PYTHONPATH=src python -m benchmarks.run admission    # + BENCH_admission.json
  PYTHONPATH=src python -m benchmarks.run binding_opt  # + BENCH_binding_opt.json
  PYTHONPATH=src python -m benchmarks.run compile      # + BENCH_compile.json
  PYTHONPATH=src python -m benchmarks.run energy       # + BENCH_energy.json
  PYTHONPATH=src python -m benchmarks.run stress       # + BENCH_stress.json (full 32x32)
  PYTHONPATH=src python -m benchmarks.run faults       # + BENCH_faults.json (failure storm)
  PYTHONPATH=src python -m benchmarks.run maxplus      # + BENCH_maxplus.json (backend sweep)
  PYTHONPATH=src python -m benchmarks.run serving      # + BENCH_serving.json (burst admissions)

The design-space sweep benchmark (batched Max-Plus vs per-graph loop)
lives in its own module:  PYTHONPATH=src python -m benchmarks.sweep
"""

from __future__ import annotations

import sys
import time

from repro.compile_cache import configure_compile_cache


def main() -> None:
    configure_compile_cache()
    from . import paper_figures, roofline

    want = sys.argv[1] if len(sys.argv) > 1 else None
    for name, fn in paper_figures.ALL.items():
        if want and want not in name:
            continue
        t0 = time.perf_counter()
        rows = fn()
        dt = time.perf_counter() - t0
        print(f"\n# {name}  ({dt:.1f}s)")
        for row in rows:
            print(",".join(str(x) for x in row))

    if want is None or "admission" in want:
        from . import admission

        t0 = time.perf_counter()
        rows, summary, _ = admission.run()
        print(f"\n# admission  ({time.perf_counter() - t0:.1f}s)")
        for row in rows:
            print(",".join(str(x) for x in row))
        print("##", summary)

    if want is None or "binding_opt" in want:
        from . import binding_opt

        t0 = time.perf_counter()
        rows, summary, _ = binding_opt.run()
        print(f"\n# binding_opt  ({time.perf_counter() - t0:.1f}s)")
        for row in rows:
            print(",".join(str(x) for x in row))
        print("##", summary)

    if want is None or "compile" in want:
        from . import compile_latency

        t0 = time.perf_counter()
        rows, summary, _ = compile_latency.run()
        print(f"\n# compile_latency  ({time.perf_counter() - t0:.1f}s)")
        for row in rows:
            print(",".join(str(x) for x in row))
        print("##", summary)

    if want is None or "energy" in want:
        from . import energy

        t0 = time.perf_counter()
        rows, summary, _ = energy.run()
        print(f"\n# energy  ({time.perf_counter() - t0:.1f}s)")
        for row in rows:
            print(",".join(str(x) for x in row))
        print("##", summary)

    if want is None or "stress" in want:
        from . import stress

        t0 = time.perf_counter()
        rows, summary, _ = stress.run(smoke=want is None)
        print(f"\n# stress  ({time.perf_counter() - t0:.1f}s)")
        for row in rows:
            print(",".join(str(x) for x in row))
        print("##", summary)

    if want is None or "faults" in want:
        from . import faults

        t0 = time.perf_counter()
        rows, summary, _ = faults.run(smoke=want is None)
        print(f"\n# faults  ({time.perf_counter() - t0:.1f}s)")
        for row in rows:
            print(",".join(str(x) for x in row))
        print("##", summary)

    if want is None or "maxplus" in want:
        from . import maxplus_backends

        t0 = time.perf_counter()
        rows, summary, _ = maxplus_backends.run(smoke=want is None)
        print(f"\n# maxplus_backends  ({time.perf_counter() - t0:.1f}s)")
        for row in rows:
            print(",".join(str(x) for x in row))
        print("##", summary)

    if want is None or "serving" in want:
        from . import serving

        t0 = time.perf_counter()
        rows, summary, _ = serving.run(smoke=want is None)
        print(f"\n# serving  ({time.perf_counter() - t0:.1f}s)")
        for row in rows:
            print(",".join(str(x) for x in row))
        print("##", summary)

    if want is None or "roofline" in want:
        print("\n# roofline_single_pod (from dry-run artifacts)")
        for row in roofline.rows("256"):
            print(",".join(str(x) for x in row))
        print("\n# dominant bottleneck counts:", roofline.bottleneck_summary())


if __name__ == "__main__":
    main()
