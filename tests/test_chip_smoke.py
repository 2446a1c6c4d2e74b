"""``chip_smoke.py`` rehearsed on the CPU at scale 8.

The smoke refuses any platform but a TPU; these tests steer that check
here (never through an option of the script) and run its phases, which
compare BFS, PageRank and the served queries against the plain oracles
in ``core/reference.py`` and raise on any mismatch.
"""
import json
import os
import sys
import types

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))

import chip_smoke  # noqa: E402
from repro.core import algorithms as alg  # noqa: E402


def test_smoke_refuses_a_cpu(capsys, monkeypatch):
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [cpu])
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code != 0 and "'cpu'" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_phases_match_oracles_at_scale_8(capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda chips: jax.devices())
    assert chip_smoke.main(["--scale", "8", "--tiles", "4"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    phases = [ln.split()[0] for ln in lines[2:-1]]
    assert phases == ["phase=bfs", "phase=pagerank", "phase=serve"]
    dev = jax.devices()[0]
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}


def test_smoke_spmd_phase_on_one_device():
    """The --chips 4 comparison logic (values and every Stats field of the
    shard_map run against the LocalComm twin) on the one device here."""
    g = chip_smoke.build_graph(7, seed=1)
    pg = alg.prepare(g, T=1)
    root = chip_smoke.pick_vertices(g, 1, np.random.default_rng(1))[0]
    mesh = jax.make_mesh((1,), ("x",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    out = chip_smoke.phase_spmd(g, pg, chip_smoke.engine_config(1), root, mesh)
    assert out["rounds"] > 0 and out["edges_scanned"] > 0
