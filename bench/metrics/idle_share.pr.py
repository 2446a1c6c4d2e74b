"""idle_share.pr: the device's idle share of the traced window (one whole
engine call and the host work before it, bench/run.py Profiler): one less
the union of its operations' intervals over the window, averaged over the
chips used (pagerank cells)."""


def read(run):
    if run.trace is None or run.kind != "pagerank":
        return None
    return run.trace["idle_share"]
