"""Mean seconds of one batched free-tile subset scoring in the window (host
span around ``repro.core.explore.score_free_tile_subsets``)."""


def read(run):
    if run.spans is None:
        return None
    total, count = run.spans.total("subset_scoring", run.window.t_open,
                                   run.window.t_end)
    return total / count if count else None
