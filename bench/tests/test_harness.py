"""The harness finds everything by name, keeps to the result contract's
names and budget, and refuses to measure anything but a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run

BENCH = run.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def names():
    out = [("config", c["name"]) for c in BENCH["configs"]]
    out += [("reduced", k) for c in BENCH["configs"] for k in c["reduced"]]
    out += [("workload", w["name"]) for w in BENCH["workloads"]]
    out += [("traffic", w["traffic"]) for w in BENCH["workloads"]]
    out += [("metric", m["name"]) for m in METRICS]
    return out


@pytest.mark.parametrize("kind,name", names())
def test_names_use_only_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_units_and_fields_are_well_formed(metric):
    m = next(m for m in METRICS if m["name"] == metric)
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)
    if m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        # every cell it lists reports the end-to-end metric it moves
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", WORKLOADS)) <= set(
            moved.get("workloads", WORKLOADS))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_its_files_by_name(workload):
    cell, config, traffic = run.resolve(BENCH, workload)
    assert config["name"] == cell["config"]
    assert traffic["algorithm"] in ("bfs", "pagerank")
    cfg_entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert cfg_entry["file"] == f"bench/configs/{cell['config']}.json"
    assert set(cfg_entry["reduced"]) <= set(config) | set(config["reduced"])
    e2e = [m["name"] for m in run.metrics_of(BENCH, workload, False)]
    per_layer = run.metrics_of(BENCH, workload, True)
    assert "setup_s" in e2e and len(e2e) >= 2 and per_layer
    for m in run.metrics_of(BENCH, workload, False) + per_layer:
        assert callable(run.load_metric(m["name"]).read)


def test_benchmark_keys_and_budget():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    rs = BENCH["run_seconds"]
    # a full check of 24 cells, each run with its allowance, fits 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.fixture
def copy_of_bench(tmp_path, monkeypatch):
    """The benchmark's files in a fresh directory, with the harness
    looking there."""
    shutil.copytree(os.path.join(run.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    return tmp_path


@pytest.mark.parametrize("new", ["config", "traffic", "metric"])
def test_new_files_are_found_without_editing_old_ones(copy_of_bench, new):
    root = copy_of_bench
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = dict(next(w for w in bench["workloads"]
                     if w["name"] == "kron17-bfs"), name="added-cell")
    if new == "config":
        cfg = dict(run.load_config(cell["config"]), name="added-config",
                   scale=9)
        (root / "bench" / "configs" / "added-config.json").write_text(
            json.dumps(cfg))
        cell["config"] = "added-config"
    elif new == "traffic":
        mix = dict(run.load_traffic(cell["traffic"]), search_keys=3)
        (root / "bench" / "traffic" / "added_mix.json").write_text(
            json.dumps(mix))
        cell["traffic"] = "added_mix"
    else:
        (root / "bench" / "metrics" / "added_metric.py").write_text(
            "def read(run):\n    return 1.5\n")
        bench["per_layer"].append(
            {"name": "added_metric", "unit": "s", "better": "lower",
             "source": "host_clock", "layer": "graph build",
             "moves": "setup_s", "workloads": ["added-cell"]})
    bench["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    bench = run.load_benchmark()
    _, config, traffic = run.resolve(bench, "added-cell")
    if new == "config":
        assert config["name"] == "added-config" and config["scale"] == 9
    elif new == "traffic":
        assert traffic["search_keys"] == 3
    else:
        names = [m["name"] for m in run.metrics_of(bench, "added-cell", True)]
        assert "added_metric" in names
        assert run.load_metric("added_metric").read(None) == 1.5
    assert all(p.read_bytes() == b for p, b in before.items())


def run_py(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_run_refuses_a_cpu_and_prints_no_result(workload):
    p = run_py(run.ROOT, workload)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


def test_run_refuses_a_checkout_without_the_program(copy_of_bench):
    p = run_py(copy_of_bench, WORKLOADS[0])
    assert p.returncode != 0 and "src" in p.stderr
    assert '"metrics"' not in p.stdout
