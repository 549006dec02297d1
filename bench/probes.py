"""What the harness observes of the program while it runs.

Wrappers installed around the program's own functions, removed by
``close()``.  They change no argument and no result.

* ``SolveProbe``: one record per exact device solve (the ``"csr-jit"``
  path of ``repro.core.maxplus.mcr_batch``): its shape, tolerance and the
  devices its result arrays live on, and, for a sample of the solves in the
  window drawn from the seed, the solved stack and the periods returned.
  Grown from the dispatch log of ``chip_smoke.py``.
* ``Spans``: host spans around the calls into each layer, written into the
  profiler trace as ``jax.profiler.TraceAnnotation`` and kept in memory.
* ``CompileCounter``: XLA backend compiles and persistent-cache hits, from
  ``jax.monitoring`` events.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np


class _Patches:
    def __init__(self):
        self._undo = []

    def set(self, obj, name, value):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def close(self):
        for obj, name, old in reversed(self._undo):
            setattr(obj, name, old)
        self._undo.clear()


class SolveProbe:
    """Records every exact device solve; keeps a seeded sample of them."""

    def __init__(self, *, keep: int, seed: int):
        from repro.core import maxplus
        from repro.kernels import maxplus_bellman as kbell

        self.calls: list[dict] = []
        self.kept: list[dict] = []
        self.largest: dict | None = None
        self.in_window = False
        self._keep = int(keep)
        self._rng = np.random.default_rng(seed)
        self._seen = 0
        self._patch = _Patches()
        csr = maxplus._mcr_batch_csr
        dispatch = kbell._dispatch_bisect

        def solve(stack, **kw):
            call = {
                "rows": stack.n_graphs, "n": stack.n_actors,
                "edges": stack.n_edges, "rel_tol": kw.get("rel_tol", 1e-8),
                "devices": [], "in_window": self.in_window,
            }
            self.calls.append(call)
            periods = csr(stack, **kw)
            if self.in_window:
                self._sample({**call, "stack": stack,
                              "periods": np.array(periods)})
            return periods

        def recording_dispatch(*args, **kw):
            out = dispatch(*args, **kw)
            self.calls[-1]["devices"].extend(out[0].devices())
            return out

        self._patch.set(maxplus, "_mcr_batch_csr", solve)
        self._patch.set(kbell, "_dispatch_bisect", recording_dispatch)

    def _sample(self, rec: dict) -> None:
        size = rec["rows"] * rec["edges"]
        if self.largest is None or size > self.largest["rows"] * \
                self.largest["edges"]:
            self.largest = rec
        self._seen += 1
        if len(self.kept) < self._keep:
            self.kept.append(rec)
        else:
            j = int(self._rng.integers(self._seen))
            if j < self._keep:
                self.kept[j] = rec

    def sample(self) -> list[dict]:
        """The kept solves of the window, the largest among them."""
        out = list(self.kept)
        if self.largest is not None and all(r is not self.largest
                                            for r in out):
            out.append(self.largest)
        return out

    def window_calls(self) -> list[dict]:
        return [c for c in self.calls if c["in_window"]]

    def close(self) -> None:
        self._patch.close()


class Spans:
    """Host spans around the calls into the program's layers."""

    def __init__(self, ctl, *, annotate: bool):
        import jax

        from repro.core import engine, explore, maxplus
        from repro.kernels import maxplus_bellman as kbell

        self.records: list[tuple[str, float, float]] = []
        self._annotate = annotate
        self._jax = jax
        self._patch = _Patches()

        def wrap(name, fn):
            def wrapped(*args, **kw):
                with self.span(name):
                    return fn(*args, **kw)
            return wrapped

        p = self._patch
        p.set(ctl, "admit", wrap("admit", ctl.admit))
        p.set(ctl, "evict", wrap("evict", ctl.evict))
        p.set(explore, "score_free_tile_subsets",
              wrap("subset_scoring", explore.score_free_tile_subsets))
        p.set(engine, "stack_hardware_aware",
              wrap("stack_build", engine.stack_hardware_aware))
        p.set(maxplus, "_pack_csr_chunk",
              wrap("pack", maxplus._pack_csr_chunk))
        p.set(kbell, "mcr_bisect_device",
              wrap("device_solve", kbell.mcr_bisect_device))

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        ann = (self._jax.profiler.TraceAnnotation(f"bench.{name}")
               if self._annotate else contextlib.nullcontext())
        try:
            with ann:
                yield
        finally:
            self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, t0: float, t1: float) -> tuple[float, int]:
        """(seconds, count) of the ``name`` spans that start in [t0, t1)."""
        ds = [b - a for n, a, b in self.records
              if n == name and t0 <= a < t1]
        return float(sum(ds)), len(ds)

    def close(self) -> None:
        self._patch.close()


class CompileCounter:
    """XLA backend compiles and compile-cache hits, stamped with the time."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring

        self.compiles: list[tuple[float, float]] = []
        self.cache_hits: list[float] = []
        self._monitoring = monitoring

        def on_duration(event, duration, **_):
            if event == self.COMPILE:
                self.compiles.append((time.perf_counter(), float(duration)))

        def on_event(event, **_):
            if event == self.CACHE_HIT:
                self.cache_hits.append(time.perf_counter())

        self._on_duration, self._on_event = on_duration, on_event
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def count(self, t0: float, t1: float) -> tuple[int, int]:
        """(backend compiles, cache hits) stamped in [t0, t1)."""
        return (sum(1 for t, _ in self.compiles if t0 <= t < t1),
                sum(1 for t in self.cache_hits if t0 <= t < t1))

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on_duration)
        self._monitoring.unregister_event_listener(self._on_event)
