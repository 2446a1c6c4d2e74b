"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` and each metric's reader in
``bench/metrics/<metric>.py``.  A run:

1. refuses any platform but a TPU, and fewer chips than the cell asks
   for: it exits non-zero and prints no result;
2. builds the configuration's graph (``bench/graph500.py``, from its
   ``graph_seed``) and partitions it with the program's ``alg.prepare``;
   the traffic draws its inputs from ``--seed``;
3. warms up the cell's own programs with one call that does next to no
   work (``bench/traffic.py``);
4. runs the traffic as a closed loop until ``--seconds`` have passed, and
   lets the call in flight finish;
5. with ``--trace 1``, profiles one engine call of that window
   (``Profiler``) and reduces the trace (``bench/trace.py``);
6. compares every call's output with the benchmark's own reference
   (``bench/check.py``), once the window has closed;
7. prints the numbers compared beside their limits as the last lines of
   standard error, and one JSON object as the last line of standard
   output: the end-to-end metrics (``--trace 0``) or the per-layer ones
   (``--trace 1``).

``setup_s`` runs from the start of this script to the first timed call.
JAX's persistent compilation cache is kept in
``<checkout>/.jax_compilation_cache`` unless ``JAX_COMPILATION_CACHE_DIR``
says otherwise, so that only a cell's first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
sys.path[:0] = [p for p in (ROOT, SRC) if p not in sys.path]

SPANS = ("window", "unit", "engine_call", "to_original")


def log(**kv):
    print(" ".join(f"{k}={v}" for k, v in kv.items()), file=sys.stderr,
          flush=True)


# ------------------------------------------------------------- lookup by name

def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, "bench", kind, f"{name}.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _load_json("configs", name)


def load_traffic(name: str) -> dict:
    return _load_json("traffic", name)


def load_metric(name: str):
    """The reader module ``bench/metrics/<name>.py`` (its ``read(run)``)."""
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries a cell reports: its end-to-end ones, or with
    ``trace`` its per-layer ones (those listing it, or listing none)."""
    entries = bench["per_layer" if trace else "end_to_end"]
    return [m for m in entries if workload in m.get("workloads", [workload])]


def resolve(bench: dict, workload: str):
    """(cell, configuration, traffic mix) of ``workload``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    return cell, load_config(cell["config"]), load_traffic(cell["traffic"])


# ------------------------------------------------------------------- devices

def require_devices(chips: int) -> list:
    """The TPU devices, or exit non-zero: no other platform is measured."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU, but JAX's platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def use_compile_cache():
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_compilation_cache"))
    # every program of the cell, however small, is read back next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ------------------------------------------------------------------ the run

@dataclasses.dataclass
class Run:
    """What the metric readers read (``bench/metrics/*.py``)."""

    kind: str               # the traffic's algorithm
    units: list             # traffic.Unit, in the order issued
    window_start: float     # host clock, s
    window_end: float       # end of the last unit
    setup_s: float
    build_s: float
    trace: dict | None      # bench.trace.reduce of the window, or None


class Profiler:
    """The traced run's profile: the window's second engine call.

    The host driver's engine call (``alg._call``) and result copy
    (``alg.to_original``) are wrapped in spans.  The profile starts as the
    window's first engine call returns and stops as the second returns,
    so it holds one whole engine call and the host work before it: the
    previous unit's result copy and the next one's set-up, or PageRank's
    update between epochs.  Whole calls let ``round_ms`` divide by the
    rounds in the trace."""

    def __init__(self, alg, logdir: str):
        self.alg, self.logdir = alg, logdir
        self.calls, self.rounds, self.taken = 0, None, False
        self.saved = alg._call, alg.to_original

    def __enter__(self):
        from jax.profiler import TraceAnnotation

        def call(*a, **kw):
            self.calls += 1
            second = self.calls == 2
            try:
                with TraceAnnotation("engine_call"):
                    out = self.saved[0](*a, **kw)
            finally:
                if second and self.window is not None:
                    self._stop()
            if self.calls == 1:
                self._start()
            elif second:
                self.taken = True
                self.rounds = int(out[2].rounds)
            return out

        def to_original(*a, **kw):
            with TraceAnnotation("to_original"):
                return self.saved[1](*a, **kw)

        self.window = None
        self.alg._call, self.alg.to_original = call, to_original
        return self

    def __exit__(self, *exc):
        self.alg._call, self.alg.to_original = self.saved
        if self.window is not None:       # a window cut short
            self._stop()
            self.taken = False

    def _start(self):
        import jax

        jax.profiler.start_trace(self.logdir)
        self.window = jax.profiler.TraceAnnotation("window")
        self.window.__enter__()

    def _stop(self):
        import jax

        self.window.__exit__(None, None, None)
        self.window = None
        jax.profiler.stop_trace()


def engine_config(config: dict):
    from repro.core import engine
    from repro.core.program import as_program, sized_cfg

    eng = dict(config["engine"])
    size_for = eng.pop("size_queues_for")
    cfg = engine.EngineConfig(**eng)
    return sized_cfg(cfg, as_program(getattr(engine, size_for.upper())),
                     config["tiles"])


def make_mesh(config: dict, devices):
    import jax

    if config.get("mesh_axis") is None:
        return None
    return jax.make_mesh((config["tiles"],), (config["mesh_axis"],),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices[:config["tiles"]])


def run_cell(cell: dict, config: dict, traffic_mix: dict, metrics: list,
             seed: int, seconds: float, trace: bool, devices) -> dict:
    """One run of a cell on ``devices``: its result line as a dict."""
    from jax.profiler import TraceAnnotation

    from bench import check, graph500, trace as tr, traffic
    from repro.core import algorithms as alg

    use_compile_cache()
    used = devices[:cell["chips"]]
    clock = time.perf_counter
    t0 = clock()
    g = graph500.build(config)
    t1 = clock()
    pg = alg.prepare(g, T=config["tiles"])
    cfg = engine_config(config)
    build_s = clock() - t0
    log(phase="build", scale=config["scale"], V=g.num_vertices,
        E=g.num_edges, T=pg.T, e_chunk=pg.e_chunk, v_chunk=pg.v_chunk,
        gen_s=t1 - t0, prepare_s=clock() - t1)
    mesh = make_mesh(config, used)
    loop = traffic.Loop(traffic_mix, g, pg, cfg, mesh, seed)
    t0 = clock()
    loop.warmup()
    log(phase="warmup", warmup_s=clock() - t0)

    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    profile = Profiler(alg, logdir) if trace \
        else contextlib.nullcontext()
    units = []
    with profile:
        start = clock()
        setup_s = start - T_START
        # a traced run goes on until it has made its second engine call
        while (not units or clock() - start < seconds
               or (trace and profile.calls < 2)):
            with TraceAnnotation("unit"):
                units.append(loop.unit(len(units), clock))
    window_end = units[-1].end
    log(phase="window", units=len(units), window_s=window_end - start,
        rounds=[u.rounds for u in units],
        unit_s=[round(u.end - u.start, 4) for u in units])
    memory = peak_bytes(used)

    reduced = None
    if trace and profile.taken:
        t0 = clock()
        reduced = tr.reduce(tr.load(logdir, SPANS))
        reduced["rounds"] = profile.rounds
        log(phase="trace", read_s=clock() - t0, ops=reduced["op_count"],
            rounds=profile.rounds, busy_s=reduced["busy_s"],
            window_s=reduced["window_s"])

    if trace:
        shutil.rmtree(logdir, ignore_errors=True)
    # the program's state goes before the reference runs
    del loop, pg, mesh
    gc.collect()
    for u in units:
        if u.error:
            log(unit_error=u.error)
    checks, failed = check.compare(traffic_mix["algorithm"], traffic_mix, g,
                                   units)
    run = Run(traffic_mix["algorithm"], units, start, window_end, setup_s,
              build_s, reduced)
    values = {}
    for m in metrics:
        v = load_metric(m["name"]).read(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": check.verdict(checks, failed),
           "attempted": len(units), "failed": failed, "metrics": values,
           "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"run.py: the program is not in this checkout "
                         f"({SRC}/repro is missing)")
    bench = load_benchmark()
    cell, config, traffic_mix = resolve(bench, args.workload)
    devices = require_devices(cell["chips"])
    out = run_cell(cell, config, traffic_mix,
                   metrics_of(bench, args.workload, bool(args.trace)),
                   args.seed, args.seconds, bool(args.trace), devices)
    for name, c in out["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
