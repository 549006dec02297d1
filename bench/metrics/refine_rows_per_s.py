"""Candidate rows a refinement solves exactly per second of refinement:
the summed ``rows`` of the window's ``refine`` spans over their summed
seconds.  Falls with a slower solve, not with a smaller search."""

from bench.metrics.refine_s import refine_spans


def read(run):
    spans = refine_spans(run)
    seconds = sum(s.seconds for s in spans)
    if seconds <= 0:
        return None
    return sum(s.attrs["rows"] for s in spans) / seconds
