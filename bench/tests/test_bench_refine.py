"""Cell ``table1.refine``: its files resolve, its loop hands the program's
recorder to the readers, the readers read it, and a small full-chip run
with the exact solve forced onto the jitted path is correct, while the
float32 control in the program's place is not."""

import json
from pathlib import Path

import pytest

from bench import run as bench_run
from bench.loops import Window
from bench.metrics import refine_rows_per_s, refine_s
from bench.run import RunRecord
from bench.tests.test_bench_faults import jitted_solve  # noqa: F401
from bench.tests.test_bench_reference import SMALL_APPS

ROOT = Path(__file__).resolve().parents[2]


def test_load_cell_finds_config_mix_and_loop():
    bench, cell, config, mix = bench_run.load_cell("table1.refine")
    assert cell["config"] == config["name"] == "table1_dynap16_refine"
    assert config["controller"] == {"placement": "isolated",
                                    "optimize_budget": [1, 8]}
    assert mix["loop"] == "recorded_rounds" and mix["max_resident"] == 4
    assert (ROOT / "bench" / "traffic" / f"{mix['loop']}.py").is_file()
    # four residents of four tiles fill the chip: every admission gets
    # exactly the tiles its eviction freed
    n = config["hardware"]["n_tiles"]
    assert mix["max_resident"] * config["tenants"]["tiles_per_tenant"] == n
    names = {m["name"] for m in bench_run.cell_metrics(
        bench, "table1.refine", "per_layer")}
    assert {"refine_s.rate", "refine_rows_per_s.rate"} <= names
    assert "subset_scoring_s.rate" not in names


class Span:
    def __init__(self, name, seconds, **attrs):
        self.name, self.seconds, self.attrs = name, seconds, attrs


class Recorder:
    def __init__(self, spans, counters):
        self.spans, self.counters = spans, counters


def record(window):
    return RunRecord(window=window, setup_s=1.0)


def test_readers_read_the_refine_spans_and_rows():
    w = Window(t_open=0.0, t_close=10.0, t_end=10.0)
    w.program = Recorder(
        [Span("device_solve", 1.0), Span("refine", 2.0, rows=40),
         Span("admit", 5.0), Span("refine", 4.0, rows=50)],
        {"solve.calls": 6})
    assert refine_s.read(record(w)) == pytest.approx(3.0)
    assert refine_rows_per_s.read(record(w)) == pytest.approx(15.0)


def test_readers_return_none_without_refine_spans():
    plain = Window(t_open=0.0, t_close=10.0, t_end=10.0)
    bare = Window(t_open=0.0, t_close=10.0, t_end=10.0)
    bare.program = Recorder([Span("admit", 5.0)], {"solve.calls": 3})
    for w in (plain, bare):
        assert refine_s.read(record(w)) is None
        assert refine_rows_per_s.read(record(w)) is None


def small_full_chip():
    """Three small Table-1 apps rotating through a full 8-tile chip, two
    residents of four tiles, refined with a (2, 8) budget."""
    _, _, config, mix = bench_run.load_cell("table1.refine")
    config["hardware"]["n_tiles"] = 8
    config["tenants"]["apps"] = list(SMALL_APPS)
    config["controller"]["optimize_budget"] = [2, 8]
    return config, {**mix, "max_resident": 2}


METRICS = ("admissions_per_s", "refine_s.rate", "refine_rows_per_s.rate")
BENCH = {"end_to_end": [{"name": m, "unit": "-"} for m in METRICS],
         "per_layer": []}


def run(control=False):
    config, mix = small_full_chip()
    return bench_run.run_cell(
        BENCH, {"name": "small", "chips": 1}, config, mix,
        seed=2**31 + 11, seconds=1.0, control=control,
        require_accelerator=False, log=lambda *_: None)


def test_recorded_loop_hands_its_recorder_to_the_window(jitted_solve):
    from bench import deploy, loops

    config, mix = small_full_chip()
    dep = deploy.build(config)
    loop = loops.make(dep, mix, 5, 1.0)
    loop.warmup()
    w = loop.run(1.0)
    rec = w.program
    refines = [s for s in rec.spans if s.name == "refine"]
    admits = sum(1 for r in w.requests if r.status == "ok")
    assert admits >= 1 and len(refines) == admits
    assert rec.counters["solve.shared_calls"] == 0
    assert rec.counters["solve.calls"] == 3 * admits
    assert all(s.attrs["rows"] > 0 for s in refines)
    assert all(s.attrs["period"] <= s.attrs["seed_period"] for s in refines)


def test_sound_run_is_correct_and_reads_its_metrics(jitted_solve):
    res = run()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(METRICS)


def test_control_in_the_programs_place_is_not_correct(jitted_solve):
    res = run(control=True)
    assert not res["correct"], res["checks"]
