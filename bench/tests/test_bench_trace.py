"""Trace reduction on a small recorded trace with known busy and idle."""

import pytest

from bench import trace

MS = 1_000_000
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def events():
    return [
        (HOST, "python", "bench.window", 0, 100 * MS),
        (HOST, "python", "bench.flush", 40 * MS, 30 * MS),
        (HOST, "python", "bench.device_solve", 45 * MS, 10 * MS),
        (DEV, "XLA Modules", "jit_csr_bisect(3)", 10 * MS, 20 * MS),
        (DEV, "XLA Ops", "while", 10 * MS, 15 * MS),
        (DEV, "XLA Ops", "gather", 20 * MS, 10 * MS),   # overlaps: union
        (DEV, "XLA Modules", "jit_csr_bisect(3)", 50 * MS, 5 * MS),
        (DEV, "XLA Ops", "gather", 50 * MS, 5 * MS),
        (DEV, "XLA Ops", "fusion", 95 * MS, 10 * MS),   # clipped at 100
        (DEV, "XLA Ops", "fusion", 150 * MS, 10 * MS),  # outside
    ]


def test_busy_idle_and_programs():
    r = trace.reduce(events())
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [10, 30) + [50, 55) + [95, 100) = 30 ms
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["program_s"] == {"csr_bisect": pytest.approx(0.025)}
    ops = dict(r["device_ops"])
    assert ops["while"] == pytest.approx(0.015)
    assert ops["gather"] == pytest.approx(0.015)
    assert ops["fusion"] == pytest.approx(0.005)
    gaps = dict(r["idle_gaps"])
    # gaps: [0,10) idle, [30,50) mid 40 -> flush, [55,95) mid 75 -> idle
    assert gaps == {"idle": pytest.approx(0.050),
                    "flush": pytest.approx(0.020)}


def test_innermost_span_names_the_gap():
    evs = events() + [(DEV, "XLA Ops", "x", 0, 40 * MS),
                      (DEV, "XLA Ops", "x", 55 * MS, 45 * MS)]
    gaps = dict(trace.reduce(evs)["idle_gaps"])
    # one gap [40, 50): its middle 45 lies in device_solve inside flush
    assert gaps == {"device_solve": pytest.approx(0.010)}


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(events()[1:])


def test_op_and_program_names():
    assert trace.op_name("%while.70 = (f32[48]{0:T(128)}) while(t)") == \
        "while.70"
    assert trace.program_name("jit_csr_bisect(17)") == "csr_bisect"
    assert trace.program_name("fusion.3") == "fusion.3"
