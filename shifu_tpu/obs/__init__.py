"""Unified telemetry subsystem: metrics registry, run journal, span tracing,
cross-host aggregation.

The single observability layer every subsystem writes into (ISSUE 1),
replacing the siloed successors of the reference's 4-hop metric funnel
(SURVEY.md section 5.5).  Three pillars:

- **metrics** (obs/metrics.py): process-local counters / gauges /
  histograms with label sets, exported as a Prometheus text scrape file.
- **journal** (obs/journal.py): append-only JSONL event stream — run
  metadata, epochs, checkpoints, restarts, cache hits, spans — written
  through data/fsio so gs:// / mock:// job dirs work like the board.
- **spans** (obs/spans.py): `with obs.span("epoch/eval"):` nested phase
  timing feeding both of the above.

Sinks are configured once per process (`configure(metrics_dir)`, or lazily
from SHIFU_TPU_METRICS_DIR via `configure_from_env`); until then the
registry still collects in memory and `event()` is a no-op, so
instrumented call sites never need to know whether telemetry is on.
`obs/aggregate.py` adds the cross-host skew table (one allgather per
epoch); `obs/render.py` renders a job's telemetry for `shifu-tpu metrics`
and `shifu-tpu profile`.  On top of the pillars, ISSUE 3 adds
`obs/introspect.py` (per-compiled-program XLA cost/memory capture,
`xla_compile` events) and `obs/goodput.py` (the per-epoch goodput
ledger: wall time classified into compile / input / step / checkpoint /
restore / eval / other buckets) — docs/OBSERVABILITY.md "Goodput
ledger".  ISSUE 6 opens the `step` bucket
itself: `obs/devprof.py` + `obs/tracefmt.py` (the device flight
recorder — per-kernel device-time rollups from scheduled jax.profiler
windows, roofline attribution, HBM watermarks, and an anomaly-triggered
one-shot trace), rendered by `shifu-tpu trace`.
"""

from __future__ import annotations

from . import (aggregate, devprof, drift, goodput,  # noqa: F401
               introspect, journal, metrics, render, sketch, slo,
               spans, tracefmt, timeline, tracing)
from ._sinks import (ENV_METRICS_DIR, SCRAPE_FILE, configure,  # noqa: F401
                     configure_from_env, event, flush, get_journal,
                     metrics_dir, reset_for_tests, resolve_metrics_dir,
                     set_journal, shutdown)
from .journal import RunJournal, read_journal, tail_journal  # noqa: F401
from .metrics import (MetricsRegistry, counter, default_registry,  # noqa: F401
                      gauge, histogram)
from .spans import current_path, span  # noqa: F401
