"""Closed loop in rounds: one operator admits the tenants back to back.

The operator has a backlog: a request goes out as soon as the previous
one returns.  A round admits every tenant once, in one order drawn from
the seed that every round repeats.  Before an admission the oldest
resident is evicted when ``max_resident`` are resident; that eviction is
part of the request.  Set-up runs one round, which places each tenant
once and compiles every solve shape the window meets.  The window starts
rounds until ``seconds`` have passed; the round in flight at the close
runs to its end and all of it counts, so a run does whole rounds: the
same work for every seed, in another order.  Each request of a round is
due when its round starts.
"""

from __future__ import annotations

import time

from bench.loops import Request, Window, seeded_rng


class Loop:
    def __init__(self, dep, mix: dict, seed: int, seconds: float):
        self.dep, self.mix = dep, mix
        names = sorted(dep.tenants)
        self.order = [names[i] for i in
                      seeded_rng(seed, 3).permutation(len(names))]
        self.resident: list = []
        self.on_release = None

    def _request(self, name: str, due: float) -> Request:
        from repro.core import AdmissionError

        ctl = self.dep.ctl
        req = Request(kind="admit", app=name, due=due,
                      start=time.perf_counter())
        try:
            if len(self.resident) >= self.mix["max_resident"]:
                req.kind = "admit+evict"
                ctl.evict(self.resident.pop(0))
            ctl.admit(name, n_tiles_request=self.dep.requests[name])
            self.resident.append(name)
            req.status = "ok"
        except AdmissionError:
            req.status = "rejected"
        except KeyError:                 # the controller lost a resident
            req.status = "failed"
        req.done = time.perf_counter()
        return req

    def expected_residents(self) -> set:
        return set(self.resident)

    def warmup(self) -> None:
        self.warmup_s = []
        for name in self.order:
            req = self._request(name, time.perf_counter())
            self.warmup_s.append((name, req.done - req.start))

    def run(self, seconds: float) -> Window:
        t_open = time.perf_counter()
        w = Window(t_open=t_open, t_close=t_open + seconds)
        self.round_s = []
        while time.perf_counter() < w.t_close:
            due = time.perf_counter()
            for name in self.order:
                if self.on_release is not None:
                    self.on_release(time.perf_counter() - t_open)
                w.requests.append(self._request(name, due))
            self.round_s.append(time.perf_counter() - due)
        w.t_end = time.perf_counter()
        return w
