"""Mean seconds of one binding refinement in the window: the program's
``refine`` span around the throughput-in-the-loop search of an admission
(``repro.core.runtime.runtime_admit``)."""


def refine_spans(run) -> list:
    """The window's ``refine`` spans; empty where the loop kept no
    recorder or the program has no such span."""
    rec = getattr(run.window, "program", None)
    if rec is None:
        return []
    return [s for s in rec.spans if s.name == "refine"]


def read(run):
    spans = refine_spans(run)
    if not spans:
        return None
    return sum(s.seconds for s in spans) / len(spans)
