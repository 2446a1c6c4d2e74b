"""A ``--trace 1`` run, driven through ``bench/run.run_cell`` on a small
graph here (the chip check skipped): it profiles the window's second
engine call and reports every per-layer metric its cell lists, with the
device's busy and traced-window seconds and the breakdown."""
import jax
import pytest

from bench import run

BENCH = run.load_benchmark()


@pytest.mark.parametrize("workload", ["kron17-bfs", "kron17-pr"])
def test_traced_run_reports_its_per_layer_metrics(workload, monkeypatch):
    cell, config, traffic = run.resolve(BENCH, workload)
    config = dict(config, scale=8, tiles=4)
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    metrics = run.metrics_of(BENCH, workload, True)
    out = run.run_cell(cell, config, traffic, metrics, 11, 0.2, True,
                       jax.devices())
    assert out["correct"]
    assert set(out["metrics"]) == {m["name"] for m in metrics}
    assert all(v["value"] > 0 for k, v in out["metrics"].items()
               if not k.startswith("idle_share"))
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    for key in ("device_ops", "idle_gaps"):
        rows = out["breakdown"][key]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    assert list(out)[-1] == "checks"
