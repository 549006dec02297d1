"""Admissions completed in the window over the window's elapsed time; the
request in flight at the close finishes and counts."""


def read(run):
    w = run.window
    done = sum(1 for r in w.requests
               if r.status == "ok" and r.kind.startswith("admit"))
    return done / (w.t_end - w.t_open)
