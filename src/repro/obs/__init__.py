"""repro.obs — span tracing + metrics + provenance (DESIGN.md §10)."""

from repro.obs import trace
from repro.obs.drift import (DEFAULT_PHASES, DriftDetector, DriftEvent,
                             detection_bound)
from repro.obs.metrics import (TRACE2_SCHEMA, Metrics, compile_counts, dump,
                               load_jsonl, trace2_doc)
from repro.obs.provenance import provenance, runspec_hash
from repro.obs.trace import (BLOCK_SCOPES, NULL, PHASES, SCOPES,
                             TRACE_SCHEMA, Tracer, current, from_sim, phase,
                             validate)

__all__ = [
    "trace", "Tracer", "current", "from_sim", "validate", "NULL",
    "PHASES", "SCOPES", "BLOCK_SCOPES", "phase", "TRACE_SCHEMA",
    "TRACE2_SCHEMA",
    "Metrics", "compile_counts", "trace2_doc",
    "dump", "load_jsonl", "provenance", "runspec_hash",
    "DEFAULT_PHASES", "DriftDetector", "DriftEvent", "detection_bound",
]
