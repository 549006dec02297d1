"""Metric arithmetic: rates over all the work and time of the window;
readers with nothing to read return None, never 0."""

import pytest

from bench.loops import Request, Window
from bench.metrics import (admissions_per_s, compiles_in_window,
                           device_idle_share, setup_s, solve_device_share,
                           subset_scoring_s)
from bench.run import RunRecord


def window(requests, t_close=10.0, t_end=None):
    return Window(t_open=0.0, t_close=t_close,
                  t_end=t_close if t_end is None else t_end,
                  requests=requests)


def test_admissions_per_s_over_elapsed_time():
    reqs = [Request("admit+evict", f"a{i}", due=2.0 * i, start=2.0 * i,
                    done=2.0 * i + 2.0, status="ok") for i in range(6)]
    reqs.append(Request("admit", "r", due=12.0, start=12.0, done=13.0,
                        status="rejected"))
    run = RunRecord(window=window(reqs, t_close=11.0, t_end=13.0),
                    setup_s=1.0)
    # the refused admission takes time and counts for nothing
    assert admissions_per_s.read(run) == pytest.approx(6 / 13.0)


class Spans:
    def __init__(self, records):
        self.records = records

    def total(self, name, t0, t1):
        ds = [b - a for n, a, b in self.records if n == name and t0 <= a < t1]
        return sum(ds), len(ds)


def test_subset_scoring_mean_per_call_in_the_window():
    spans = Spans([("subset_scoring", 1.0, 1.5), ("subset_scoring", 4.0, 5.5),
                   ("admit", 0.5, 6.0), ("subset_scoring", 12.0, 20.0)])
    run = RunRecord(window=window([], t_end=11.0), setup_s=1.0, spans=spans)
    assert subset_scoring_s.read(run) == pytest.approx(1.0)


@pytest.mark.parametrize("reader", [subset_scoring_s, device_idle_share,
                                    solve_device_share])
def test_nothing_to_read_is_none(reader):
    run = RunRecord(window=window([]), setup_s=1.0)
    assert reader.read(run) is None


def test_device_shares_of_the_traced_window():
    trace = {"window_s": 10.0, "busy_s": 4.0,
             "program_s": {"csr_bisect": 3.0, "other": 1.0}}
    run = RunRecord(window=window([]), setup_s=1.0, trace=trace)
    assert device_idle_share.read(run) == pytest.approx(0.6)
    assert solve_device_share.read(run) == pytest.approx(0.3)
    idle = RunRecord(window=window([]), setup_s=1.0,
                     trace={**trace, "busy_s": 0.0, "program_s": {}})
    assert device_idle_share.read(idle) is None
    assert solve_device_share.read(idle) is None


def test_setup_and_compile_counts_pass_through():
    run = RunRecord(window=window([]), setup_s=12.5, compiles_in_window=3)
    assert setup_s.read(run) == 12.5
    assert compiles_in_window.read(run) == 3
