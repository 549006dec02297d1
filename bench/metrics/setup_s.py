"""Process start to window open: loading, building, compiling, warm-up."""


def read(run):
    return run.setup_s
