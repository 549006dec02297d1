"""Share of the traced window in which the device ran the exact solve
program (``csr_bisect`` in ``repro.kernels.maxplus_bellman``)."""


def read(run):
    t = run.trace
    if t is None or t["window_s"] <= 0 or "csr_bisect" not in t["program_s"]:
        return None
    return t["program_s"]["csr_bisect"] / t["window_s"]
