"""collective_share: the share of the first device's busy time in the
traced window spent in collective operations (all-to-all, all-reduce,
collective-permute, all-gather, reduce-scatter, by HLO name).  A run on
one chip has none to read."""


def read(run):
    if run.trace is None or run.trace["collective_s"] <= 0:
        return None
    return run.trace["collective_s"] / run.trace["first_busy_s"]
