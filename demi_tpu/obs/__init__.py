"""demi_tpu.obs: unified observability — metrics registry, span tracing,
device-lane telemetry, and the continuous plane (journal / time series /
launch profiler).

The snapshot half (one switch, off by default):

  - ``metrics``: process-wide registry of labeled counters / gauges /
    timing histograms with JSON snapshot + cross-process merge;
  - ``spans``: nested ``span("stage.name", ...)`` tracing with JSONL and
    Chrome/Perfetto ``trace_event`` export, a per-name totals table
    (``stage_totals()`` / ``stage_counts()``), a row of the same stages
    for each job (``job_ledger()``), and, while a ``jax.profiler``
    session records, the same spans on its timeline as ``demi.<name>``
    (live then with no switch; the rows keep coming after it with
    nothing recorded); and, with no switch at
    all, the set-up stages and the compile ledger (``setup_ledger()`` /
    ``compile_ledger()``): where the seconds before a command's first
    job went, and which jitted functions they were spent on;
  - ``lane_stats`` (import directly — it needs jax): per-sweep device
    counters reduced on-device and pulled once per round.

Everything above is OFF by default; ``enable()`` (or ``DEMI_OBS=1``)
turns it on. Disabled call sites pay one branch. The CLI surfaces the
layer via ``demi_tpu stats`` and ``--trace-out`` / ``--stats-out``.

The continuous half (telemetry OVER TIME, not just at exit):

  - ``journal``: crash-safe, rotation-bounded JSONL round journal — one
    generation-stamped record per DPOR round / sweep chunk / minimizer
    level; attaches to a run/checkpoint dir, resumes contiguously, and
    is the wire format ``demi_tpu top`` (and a fleet coordinator) tails;
  - ``timeseries``: bounded ring of per-round registry samples with
    delta export, Prometheus text exposition (``demi_tpu stats
    --prom``), and an optional ``--metrics-port`` HTTP endpoint;
  - ``profiler``: per-launch wall attribution (trunk vs lane vs
    harvest; dispatch vs block) keyed by launch shape, persisted in
    TuningCache-compatible evidence form (``--profile-rounds N`` adds a
    jax.profiler trace window);
  - ``distributed``: pod-wide tracing — trace contexts propagated over
    the fleet/service wire, per-connection clock-offset estimation, and
    the ``demi_tpu trace stitch`` merger that joins N processes' span
    files + journals into one clock-aligned Perfetto timeline.

Measured overhead of journal + time series always-on: < 1% of round
wall on the deep raft frontier (``bench --config 11``).
"""

from . import distributed, journal, profiler, timeseries  # noqa: F401
from .metrics import (  # noqa: F401
    REGISTRY,
    MetricsRegistry,
    counter,
    describe,
    disable,
    enable,
    enabled,
    gauge,
    histogram,
    merge_snapshots,
    relabel_snapshot,
    timed,
)
from .spans import (  # noqa: F401
    TRACER,
    Tracer,
    compile_ledger,
    job_ledger,
    new_job,
    record_span,
    setup_ledger,
    span,
    stage,
    stage_count,
    stage_counts,
    stage_totals,
)

__all__ = [
    "REGISTRY",
    "MetricsRegistry",
    "TRACER",
    "Tracer",
    "compile_ledger",
    "counter",
    "describe",
    "disable",
    "distributed",
    "enable",
    "enabled",
    "gauge",
    "histogram",
    "job_ledger",
    "journal",
    "merge_snapshots",
    "new_job",
    "profiler",
    "record_span",
    "relabel_snapshot",
    "setup_ledger",
    "span",
    "stage",
    "stage_count",
    "stage_counts",
    "stage_totals",
    "timed",
    "timeseries",
]
