"""A run of the cell's loop at a small size, with the exact solve forced
onto the jitted path: sound, it is correct; with the float32 control in the
program's place, or with the timed path broken underneath, it is not."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import run as bench_run
from bench.tests.test_bench_reference import small_config

ROOT = Path(__file__).resolve().parents[2]


def mix(name, **over):
    m = json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())
    m.update(over)
    return m


#: three apps take turns on the 16-tile chip, two resident at a time
SMALL = dict(max_resident=2)
BENCH = {"end_to_end": [{"name": "setup_s", "unit": "s"}], "per_layer": []}


@pytest.fixture
def jitted_solve(monkeypatch, tmp_path):
    """``"auto"`` resolves to the jitted solve; no persistent cache."""
    import jax

    import repro.kernels.ops as ops

    monkeypatch.setattr(ops, "_on_accelerator", lambda: True)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    yield
    jax.config.update(key, old)


def run(control=False):
    return bench_run.run_cell(
        BENCH, {"name": "small", "chips": 1}, small_config("isolated"),
        mix("admit", **SMALL), seed=2**31 + 3, seconds=1.0, control=control,
        require_accelerator=False, log=lambda *_: None)


def test_sound_run_is_correct(jitted_solve):
    res = run()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_in_the_programs_place_is_not_correct(jitted_solve):
    res = run(control=True)
    assert not res["correct"], res["checks"]
    limits = mix("admit")["check"]["limits"]
    assert res["checks"]["solve_gap"]["value"] > limits["solve_gap"]


def altered_answer(monkeypatch):
    from repro.core import maxplus

    solve = maxplus._mcr_batch_csr
    monkeypatch.setattr(maxplus, "_mcr_batch_csr",
                        lambda stack, **kw: solve(stack, **kw) * (1 + 1e-6))


def half_batch(monkeypatch):
    from repro.core import maxplus

    solve = maxplus._mcr_batch_csr

    def first_half(stack, **kw):
        out = solve(stack, **kw)
        half = (out.size + 1) // 2
        if out.size > 1:
            out = out.copy()
            out[half:] = np.mean(out[:half][np.isfinite(out[:half])])
        return out

    monkeypatch.setattr(maxplus, "_mcr_batch_csr", first_half)


def state_unchanged(monkeypatch):
    from repro.core import AdmissionController

    admit = AdmissionController.admit

    def admit_every_other(self, app, **kw):
        self._bench_admits = getattr(self, "_bench_admits", 0) + 1
        if self._bench_admits % 2:
            return admit(self, app, **kw)
        return None                          # returns, state untouched

    monkeypatch.setattr(AdmissionController, "admit", admit_every_other)


@pytest.mark.parametrize("fault", [altered_answer, half_batch,
                                   state_unchanged])
def test_broken_timed_path_is_not_correct(jitted_solve, monkeypatch, fault):
    fault(monkeypatch)
    res = run()
    assert not res["correct"], res["checks"]
