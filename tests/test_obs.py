"""In-program spans and counters (``repro.obs``): nesting, request ids,
self time, and the off state with no recorder attached."""

import dataclasses
import time

import pytest

from repro import obs


def test_spans_nest_with_parent_and_request():
    with obs.recording() as rec:
        with obs.span("admit", app="a") as root:
            with obs.span("subset_scoring") as mid:
                with obs.span("bind") as leaf:
                    pass
            with obs.span("report_score") as tail:
                pass
        with obs.span("evict") as other:
            pass
    assert root.parent == 0 and root.request == root.id
    assert mid.parent == root.id and leaf.parent == mid.id
    assert tail.parent == root.id
    assert {s.request for s in (root, mid, leaf, tail)} == {root.id}
    assert other.parent == 0 and other.request == other.id != root.id
    # kept in the order they closed
    assert [s.name for s in rec.spans] == [
        "bind", "subset_scoring", "report_score", "admit", "evict"]
    assert root.attrs == {"app": "a"}


def test_self_time_by_request():
    def sp(name, sid, parent, request, t0, t1):
        return obs.Span(name=name, id=sid, parent=parent, request=request,
                        attrs={}, start_ns=t0, end_ns=t1)

    rec = obs.Recorder()
    ms = 1_000_000
    rec.spans = [
        sp("device_solve", 3, 2, 1, 2 * ms, 7 * ms),
        sp("solve", 2, 1, 1, 1 * ms, 8 * ms),
        sp("pack", 4, 1, 1, 8 * ms, 9 * ms),
        sp("admit", 1, 0, 1, 0, 10 * ms),
        sp("evict", 5, 0, 5, 10 * ms, 11 * ms),
        sp("admit", 6, 0, 6, 11 * ms, 12 * ms),
    ]
    first, second = rec.self_seconds("admit")
    assert first == pytest.approx({"admit": 0.002, "solve": 0.002,
                                   "device_solve": 0.005, "pack": 0.001})
    assert sum(first.values()) == pytest.approx(0.010)
    assert second == pytest.approx({"admit": 0.001})
    assert rec.total("admit") == (pytest.approx(0.011), 2)


def test_decorated_function_is_a_span():
    @obs.span("pack")
    def pack(x):
        """Doubles."""
        return 2 * x

    with obs.recording() as rec:
        assert pack(3) == 6 and pack(4) == 8
    assert [s.name for s in rec.spans] == ["pack", "pack"]
    assert pack.__doc__ == "Doubles."


def test_no_recorder_keeps_nothing_yet_times_the_span():
    assert obs.recorder() is None
    with obs.span("solve") as s:
        time.sleep(0.002)
    assert s.seconds >= 0.002
    obs.count("solve.calls")
    with obs.recording() as rec:
        assert obs.recorder() is rec
    assert obs.recorder() is None
    assert rec.spans == [] and rec.counters == {}


def test_counters_add():
    with obs.recording() as rec:
        obs.count("solve.calls")
        obs.count("solve.calls")
        obs.count("solve.rounds", 12)
        obs.count("solve.rounds", 4)
    obs.count("solve.calls")                 # detached: no effect
    assert rec.counters == {"solve.calls": 2, "solve.rounds": 16}


def test_span_closes_on_error():
    with obs.recording() as rec:
        with pytest.raises(ValueError):
            with obs.span("admit"):
                raise ValueError("refused")
        with obs.span("evict") as after:
            pass
    assert [s.name for s in rec.spans] == ["admit", "evict"]
    assert after.parent == 0


@pytest.fixture
def jitted_solve(monkeypatch):
    """``"auto"`` resolves to the jitted device solve, as on the chip."""
    import repro.kernels.ops as ops

    monkeypatch.setattr(ops, "_on_accelerator", lambda: True)


def test_an_admission_is_one_request_of_the_layers_spans(jitted_solve):
    from repro.core import DYNAP_SE, AdmissionController, small_app

    ctl = AdmissionController(dataclasses.replace(DYNAP_SE, n_tiles=9),
                              placement="isolated")
    snn = small_app(180, 2200, seed=50)
    snn.name = "app0"
    ctl.register(snn)
    with obs.recording() as rec:
        ctl.admit("app0", n_tiles_request=3)
        ctl.evict("app0")
    admit, evict = [s for s in rec.spans if s.parent == 0]
    assert (admit.name, evict.name) == ("admit", "evict")
    by_request = {}
    for s in rec.spans:
        by_request.setdefault(s.request, []).append(s)
    assert {s.name for s in by_request[evict.id]} == {"evict"}
    mine = by_request[admit.id]
    assert {"subset_scoring", "bind", "project", "stack_build", "solve",
            "pack", "device_solve", "report_score"} <= {s.name for s in mine}
    # every counted solve is a device_solve span of this admission, which
    # carries that call's own counts
    solves = [s for s in mine if s.name == "device_solve"]
    assert rec.counters["solve.calls"] == len(solves)
    for key in ("steps", "rounds", "relaxations"):
        assert sum(s.attrs[key] for s in solves) == \
            rec.counters[f"solve.{key}"]
