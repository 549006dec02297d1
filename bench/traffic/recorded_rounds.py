"""Closed rounds, as ``closed_rounds``, with the program's recorder on.

The loop of ``closed_rounds`` (one operator, back to back, whole rounds of
every tenant in a seed-drawn order), with ``repro.obs.recording()``
attached around the measured window.  The returned ``Window`` carries the
recorder as ``window.program``: the program's spans that closed in the
window and its counter totals, for the readers of per-layer metrics.
"""

from __future__ import annotations

from bench.traffic import closed_rounds


class Loop(closed_rounds.Loop):
    def run(self, seconds: float):
        from repro import obs

        with obs.recording() as rec:
            window = super().run(seconds)
        window.program = rec
        return window
