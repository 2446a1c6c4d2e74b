"""Flight recorder: opt-in per-round trace capture for the Dalorex engine.

``EngineConfig(trace=True, trace_every=k, trace_rounds=R)`` makes the
engine carry a :class:`TraceBuf` ring through the round loop, recording
per-channel msgs/spills/queue depth, per-tile busy cycles and the round's
critical-path tile, per-link-class flits, TSU budget decisions, HBM DMA
windows and frontier/pending population — every round (or every k-th),
bounded by the R-slot ring.  Trace-off is byte-identical to a build
without the recorder; trace-on never perturbs values or ``Stats``.

Consumers (:mod:`repro.trace.export`): Chrome/Perfetto trace JSON on the
modeled-cycle timeline, a JSONL event stream, and the utilization /
work-imbalance / queue-depth summary.  CLI::

    PYTHONPATH=src python -m repro.trace summarize [--preset rmat-small]
    PYTHONPATH=src python -m repro.trace export --out run.perfetto.json

See DESIGN.md "Tracing & observability".  The recorder prices the
*modelled* machine; where the device's own time goes is read from a
profiler trace through the engine's named scopes and the drivers' host
spans (DESIGN.md "Device legs and host spans").
"""
from repro.trace.buffer import (SERIES_FIELDS, TraceBuf, record_round,
                                zero_trace)
from repro.trace.export import (LINK_CLASS_NAMES, format_summary,
                                jsonl_rows, lane_trace, reconcile_cycles,
                                summarize, to_perfetto, trace_arrays,
                                trace_metrics, utilization, work_cov,
                                write_jsonl, write_perfetto)

__all__ = [
    "TraceBuf", "SERIES_FIELDS", "record_round", "zero_trace",
    "LINK_CLASS_NAMES", "format_summary", "jsonl_rows", "lane_trace",
    "reconcile_cycles", "summarize", "to_perfetto", "trace_arrays",
    "trace_metrics", "utilization", "work_cov", "write_jsonl",
    "write_perfetto",
]
