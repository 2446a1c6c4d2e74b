"""The comparison that decides ``correct`` fails what it must fail.

* The controls (``bench/reference.py``: a BFS that loses 1 % of its
  messages, a PageRank that holds ranks in bfloat16), run through the
  comparison as ``bench/control.py`` runs them, come out not correct on
  three seeds, at the cells' own size.
* A run driven through ``bench/run.run_cell`` (the chip check skipped, a
  small graph) comes out correct as it stands, and not correct with the
  timed path broken underneath: the engine call returning its state
  unchanged, half of the tiles' results left out, the exchange between
  tiles (or chips) left out, and one answer altered where the host driver
  produces it.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from bench import control, run
from repro.core import algorithms as alg
from repro.core.comm import LocalComm

BENCH = run.load_benchmark()
SEEDS = (3, 2**31 + 5, 40_000_000_017)


def small(workload, scale=8, tiles=4):
    cell, config, traffic = run.resolve(BENCH, workload)
    return cell, dict(config, scale=scale, tiles=tiles), traffic


@pytest.mark.parametrize("workload", ["kron17-bfs", "kron17-pr"])
@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_limit(workload, seed):
    """The control in the program's place, at the cell's own size and
    through ``bench/check.py``, is not correct: it fails a number past
    its limit (past three times it, for a limit that is not 0)."""
    _, config, traffic = run.resolve(BENCH, workload)
    out = control.control_run(config, traffic, seed)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] or traffic["algorithm"] == "bfs"
    assert out["failed"] > 0
    assert any(c["value"] > (3 * c["limit"] if c["limit"] else 0)
               for c in out["checks"].values()), out["checks"]


def _unchanged(orig):
    def call(pg, alg_, cfg, value, frontier, mesh=None, axis="x", acc=None):
        _, a, stats, trace = orig(pg, alg_, cfg, value, frontier, mesh, axis,
                                  acc)
        return value, acc if acc is not None else a * 0, stats, trace
    return call


def _half(orig):
    def call(pg, alg_, cfg, value, frontier, mesh=None, axis="x", acc=None):
        v, a, stats, trace = orig(pg, alg_, cfg, value, frontier, mesh, axis,
                                  acc)
        h = pg.T // 2
        a0 = a * 0 if acc is None else acc
        return (jax.numpy.concatenate([v[:h], value[h:]]),
                jax.numpy.concatenate([a[:h], a0[h:]]), stats, trace)
    return call


def _altered(orig, kind):
    def to_original(pg, arr):
        out = np.array(orig(pg, arr))
        i = int(np.argmin(out))
        out[i] = out[i] + 1 if kind == "bfs" else out[i] * 1.001
        return out
    return to_original


FAULTS = ("none", "state_unchanged", "half_left_out", "exchange_left_out",
          "answer_altered")


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", ["kron17-bfs", "kron17-pr"])
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    cell, config, traffic = small(workload)
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    if fault == "state_unchanged":
        monkeypatch.setattr(alg, "_call", _unchanged(alg._call))
    elif fault == "half_left_out":
        monkeypatch.setattr(alg, "_call", _half(alg._call))
    elif fault == "exchange_left_out":
        monkeypatch.setattr(LocalComm, "a2a", lambda self, x: x)
    elif fault == "answer_altered":
        monkeypatch.setattr(alg, "to_original",
                            _altered(alg.to_original, traffic["algorithm"]))
    jax.clear_caches()       # the engine is traced anew with the fault
    try:
        out = run.run_cell(cell, config, traffic,
                           run.metrics_of(BENCH, workload, False), SEEDS[1],
                           0.2, False, jax.devices())
    finally:
        jax.clear_caches()
    assert out["attempted"] >= 1
    assert out["correct"] is (fault == "none"), out["checks"]
    assert list(out)[-1] == "checks"


FOUR_DEVICES = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from bench import run
    from repro.core.comm import AxisComm
    if sys.argv[1] == "exchange_left_out":
        AxisComm.a2a = lambda self, x: x
    bench = run.load_benchmark()
    config = run.load_config("g500-kron21-4chip")
    config = dict(config, scale=8)
    cell = {{"name": "kron21x4-bfs", "config": config["name"],
             "traffic": "bfs_roots", "chips": 4}}
    out = run.run_cell(cell, config, run.load_traffic("bfs_roots"),
                       run.metrics_of(bench, "kron17-bfs", False), 17, 0.2,
                       False, jax.devices())
    print(json.dumps(out))
""")


@pytest.mark.parametrize("fault", ["none", "exchange_left_out"])
def test_four_chip_exchange_left_out_is_not_correct(fault, tmp_path):
    """The four-chip configuration (``bench/configs/g500-kron21-4chip.json``,
    whose cell waits for a four-chip machine, PERF.md) on 4 CPU devices:
    ``shard_map`` over a mesh, with the exchange between chips left out."""
    script = tmp_path / "four.py"
    script.write_text(FOUR_DEVICES.format(root=run.ROOT, src=run.SRC))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run([sys.executable, str(script), fault], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is (fault == "none"), out["checks"]
