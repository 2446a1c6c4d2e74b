"""pr_edges_per_s: directed edges of the graph times the PageRank epochs
completed in the window, over the time from the window's start to the end
of the last call."""


def read(run):
    if run.kind != "pagerank":
        return None
    return sum(u.work for u in run.units) / (run.window_end - run.window_start)
