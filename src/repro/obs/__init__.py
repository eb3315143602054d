"""Observability: tracing, tuning audit, Perfetto export.

The measurement layer under the self-tuning loop.  The tuner's contract —
reconfigure iff expected improvement beats reconfiguration cost — is only
auditable if every second of a run is attributed somewhere: serving the
traffic (decode/prefill/admission), paying for a reconfiguration
(relayout/recompile), or deliberating about one (BO fit + suggestion).
``Tracer`` collects nested monotonic-clock spans with a zero-allocation
no-op mode, and names them in a ``jax.profiler`` trace when one runs;
``TuningAudit`` records every BO decision with its predicted
reconfiguration cost and the cost actually observed, so cost-model
calibration error is a first-class metric; ``report.time_attribution``
folds both into the per-run breakdown the benchmarks publish, and
``export`` writes Chrome-trace-event JSON loadable in Perfetto.
"""
from repro.obs.audit import TuningAudit
from repro.obs.export import write_audit_jsonl, write_chrome_trace
from repro.obs.report import time_attribution
from repro.obs.trace import NOP_TRACER, SPAN_NAMES, Tracer

__all__ = ["Tracer", "NOP_TRACER", "SPAN_NAMES", "TuningAudit",
           "write_chrome_trace", "write_audit_jsonl", "time_attribution"]
