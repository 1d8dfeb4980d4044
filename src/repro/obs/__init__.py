"""``repro.obs`` — unified telemetry: metrics, tracing, events, health.

One low-overhead spine for every signal the system produces (see
``docs/OBSERVABILITY.md``):

* ``obs.metrics`` — process-local registry of counters / gauges /
  histograms with labeled series; a shared **no-op recorder** until
  ``obs.configure()`` turns it on, so instrument points cost nothing in
  the default (disabled) state and never branch inside jitted code.
* ``obs.trace`` — nestable host-side spans (``with span("fl.dispatch",
  round=t)``): always a ``jax.profiler.TraceAnnotation`` (ids as event
  stats), and with telemetry on also a ``trace.span_ms`` observation
  labeled by the span path; ``annotate_scope`` names sections of jitted
  code in XLA profiles at zero runtime cost.
* ``obs.events`` / ``obs.export`` — versioned JSONL event sink plus
  Prometheus-textfile and JSON-summary exporters.
* ``obs.health`` — compensation-state monitors computed from the
  existing pytrees: EF residual mass, global-momentum norms, achieved vs
  target compression, broadcast NaN/Inf anomalies, staleness
  percentiles.
* ``python -m repro.obs.report <events.jsonl>`` — run-report renderer.

Typical launcher wiring (what ``--obs`` does)::

    import repro.obs as obs
    obs.configure("runs/exp1")            # events -> runs/exp1/events.jsonl
    ...                                   # instrumented code records
    obs.export.write_all("runs/exp1")     # metrics.prom + summary.json
    obs.shutdown()
"""

from repro.obs import events, export, health, metrics, trace
from repro.obs.metrics import (
    NOOP,
    Recorder,
    Registry,
    configure,
    enabled,
    get,
    shutdown,
)
from repro.obs.trace import annotate_scope, span

__all__ = [
    "NOOP",
    "Recorder",
    "Registry",
    "annotate_scope",
    "configure",
    "enabled",
    "events",
    "export",
    "get",
    "health",
    "metrics",
    "shutdown",
    "span",
    "trace",
]
