"""Benchmark runner: one section per paper table/figure + the roofline
aggregation.  Prints CSV-ish rows (name, key metrics, derived)."""
from __future__ import annotations

import sys
import time


def _emit(rows):
    for r in rows:
        print(",".join(f"{k}={v}" for k, v in r.items()), flush=True)


def main() -> None:
    t0 = time.time()
    fast = "--fast" in sys.argv

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fig5_ablation, fig6_scaling, fig7_throughput,
                            fig8_noc, fig10_energy, fig11_backend,
                            fig12_serving, fig13_memspace,
                            fig14_utilization, fig15_adaptive, kern_micro,
                            lm_micro, roofline, taskgraphs,
                            work_efficiency)

    print("# fig5: optimization-ladder ablation (paper Fig. 5)")
    _emit(fig5_ablation.run(scale=8 if fast else 10, T=8 if fast else 16,
                            apps=("bfs",) if fast else fig5_ablation.APPS))
    print("# fig6: strong scaling (paper Fig. 6)")
    _emit(fig6_scaling.run(scale=10 if fast else 12,
                           tiles=(4, 16) if fast else (4, 8, 16, 32, 64)))
    print("# fig7: throughput vs tiles (paper Fig. 7)")
    _emit(fig7_throughput.run(scale=10 if fast else 12,
                              tiles=(4, 16) if fast else (4, 8, 16, 32, 64),
                              apps=("bfs",) if fast else ("bfs", "sssp")))
    print("# fig8: placement / NoC balance (paper Fig. 8-9)")
    _emit(fig8_noc.run(scale=8 if fast else 10, T=8 if fast else 16))
    print("# fig10: energy ladder, placements x topologies x policies "
          "(paper Fig. 10)")
    _emit(fig10_energy.run(
        scale=8 if fast else 10, T=8 if fast else 16,
        nocs=("ideal", "mesh", "hier") if fast else
             ("ideal", "mesh", "torus", "ruche", "hier"),
        policies=("traffic",) if fast else ("traffic", "static")))
    print("# fig11: engine execution backend, xla vs pallas tile-grid "
          "kernels (interpret; fused single-launch legs vs nofuse)")
    _emit(fig11_backend.run(
        scale=8 if fast else 10, T=8 if fast else 16,
        apps=("bfs", "spmv") if fast else fig11_backend.APPS,
        nocs=("ideal", "hier") if fast else fig11_backend.NOCS,
        repeat=1 if fast else 2))
    print("# kern-micro: pallas launch-overhead pricing (fused leg = 1 "
          "launch)")
    _emit(kern_micro.run(n_chain=8 if fast else 32,
                         size=256 if fast else 1024,
                         repeat=1 if fast else 3))
    print("# fig12: query serving — batch width x arrival pattern "
          "(queries/sec, joules/query)")
    _emit(fig12_serving.run(
        scale=8 if fast else 10, T=8 if fast else 16,
        queries=16 if fast else 64,
        widths=(1, 8) if fast else (1, 8, 64),
        arrivals=("burst",) if fast else ("burst", "poisson"),
        pallas_width=0 if fast else 8))
    print("# fig13: memory-space ladder — VMEM-resident vs HBM-streamed "
          "edge shards (double-buffered DMA windows, per-space pricing)")
    _emit(fig13_memspace.run(
        scale=8 if fast else 10, T=8 if fast else 16,
        apps=("bfs", "spmv") if fast else fig13_memspace.APPS,
        pallas=not fast))
    print("# fig14: utilization over time — flight-recorder traces across "
          "noc x placement x policy (per-round util / work CoV)")
    _emit(fig14_utilization.run(
        scale=8 if fast else 10, T=8 if fast else 16,
        ndies=(2, 2),
        combos=fig14_utilization.COMBOS[:2] if fast
        else fig14_utilization.COMBOS))
    print("# fig15: adaptive placement — telemetry-driven migration vs "
          "the static die-local baseline (observe -> migrate -> rerun)")
    _emit(fig15_adaptive.run(scale=8 if fast else 10, T=8 if fast else 16,
                             ndies=(2, 1) if fast else (2, 2)))
    print("# taskgraphs: new workloads on the generic task-program executor")
    _emit(taskgraphs.run(scale=8 if fast else 10, T=8 if fast else 16,
                         ks=(2,) if fast else (2, 3, 4)))
    print("# work-efficiency (paper Section V discussion)")
    _emit(work_efficiency.run(scale=8 if fast else 10, T=8 if fast else 16))
    print("# lm-micro: LM substrate microbenches")
    _emit(lm_micro.run())
    print("# roofline: dry-run derived, paper-faithful BASELINE (pod1)")
    _emit(roofline.run(tag=""))
    print("# roofline: dry-run derived, beyond-paper OPTIMIZED (pod1)")
    _emit(roofline.run(tag="opt"))
    print("# perf: baseline vs optimized per cell")
    _emit(roofline.before_after())
    print("# dry-run multi-pod compile proof (baseline)")
    _emit(roofline.multipod_summary(tag=""))
    print("# dry-run multi-pod compile proof (optimized)")
    _emit(roofline.multipod_summary(tag="opt"))
    print(f"# total {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
