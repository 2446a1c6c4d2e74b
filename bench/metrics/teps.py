"""teps: Graph500's traversed edges per second, all the work over all the
time: the traversed edges of every search completed in the window (from
the graph and the reference's levels, bench/check.py), summed, over the
time from the window's start to the last completion."""


def read(run):
    if run.kind != "bfs":
        return None
    return sum(u.work for u in run.units) / (run.window_end - run.window_start)
