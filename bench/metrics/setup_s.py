"""setup_s: from the start of bench/run.py to the first timed call: JAX's
start, the graph's build, compile or cache load, and the warm-up."""


def read(run):
    return run.setup_s
