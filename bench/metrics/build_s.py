"""build_s: host seconds of the graph build (generation on the device from
the seed, CSR compaction, the program's alg.prepare and engine config)."""


def read(run):
    return run.build_s
