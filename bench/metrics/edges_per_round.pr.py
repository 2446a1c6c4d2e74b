"""edges_per_round.pr: edges the engine scanned per round, from its exact
counters (Stats.edges_scanned over Stats.rounds) of the window's calls
(pagerank cells)."""


def read(run):
    rounds = sum(u.rounds for u in run.units)
    if run.kind != "pagerank" or rounds == 0:
        return None
    return sum(u.edges_scanned for u in run.units) / rounds
