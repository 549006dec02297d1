"""Per-request records, and the lookup of a traffic mix's loop.

A mix is a JSON file ``bench/traffic/<name>.json``.  Its ``"loop"`` key
names a module ``bench/traffic/<loop>.py`` that drives the chip; a new
arrival process is a new module, found by that name.  A loop module
defines ``Loop(dep, mix, seed, seconds)`` with

* ``warmup()``: set-up work that compiles every shape the window meets;
* ``run(seconds) -> Window``: the measured window;
* ``expected_residents() -> set``: the residents that the answered
  requests imply;
* ``on_release``: ``None``, or a callable that ``run`` calls with each
  request's due offset in the window before it goes out.
"""

from __future__ import annotations

import dataclasses
import importlib
import math

import numpy as np


@dataclasses.dataclass
class Request:
    kind: str                 # "admit" | "evict" | "admit+evict"
    app: str
    due: float
    start: float = math.nan   # the program picked it up
    done: float = math.nan    # answered
    status: str = "pending"   # ok | rejected | skipped | pending


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float = math.nan
    t_end: float = math.nan   # last request of the window answered
    requests: list = dataclasses.field(default_factory=list)

    def due_in_window(self) -> list:
        return [r for r in self.requests
                if self.t_open <= r.due < self.t_close]


def seeded_rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), salt])


def make(dep, mix: dict, seed: int, seconds: float):
    module = importlib.import_module(f"bench.traffic.{mix['loop']}")
    return module.Loop(dep, mix, seed, seconds)
