"""round_ms.bfs: device busy milliseconds per engine round: busy time of
the traced window (mean over the chips used) over the rounds of the
engine call it holds (bfs cells)."""


def read(run):
    if run.trace is None or run.kind != "bfs" or not run.trace["rounds"]:
        return None
    return 1e3 * run.trace["busy_s"] / run.trace["rounds"]
