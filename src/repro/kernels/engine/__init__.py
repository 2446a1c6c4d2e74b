"""Pallas execution backend for the Dalorex engine round (one grid program
= one tile; see kernel.py and DESIGN.md "Pallas backend").

Standalone kernels (``frontier_pop``/``queue_push_pop``/``edge_scan_gather``
/``edge_scan_stream``/``fold_scatter``), their pure value->value bodies
(``frontier_take``/``fifo_turn``/``queue_append``/``segment_gather``/
``segment_stream``/``scatter_body``), the single-launch fused-leg harness
(``fused_leg_call``), the platform's interpret decision
(``interpret_mode``/``require_runnable``), and trace-time launch accounting
(``launches.tally``/``launches.record``).  ``segment_stream`` /
``edge_scan_stream`` are the HBM-resident-shard form of T2: double-buffered
segment DMA windows, bit-identical in valid lanes to the VMEM-direct
gather (DESIGN.md "Memory spaces")."""
from repro.kernels.engine.kernel import (edge_scan_gather, edge_scan_stream,
                                         fifo_turn, fold_scatter,
                                         frontier_pop, frontier_take,
                                         fused_leg_call, interpret_mode,
                                         queue_append, queue_push_pop,
                                         require_runnable, scatter_body,
                                         segment_gather, segment_stream)
from repro.kernels.engine.launches import record, tally

__all__ = ["edge_scan_gather", "edge_scan_stream", "fold_scatter",
           "frontier_pop", "queue_push_pop", "frontier_take", "fifo_turn",
           "queue_append", "segment_gather", "segment_stream",
           "scatter_body", "fused_leg_call", "interpret_mode",
           "require_runnable", "record", "tally"]
