"""repro.obs — stack-wide observability on the serving stack's virtual clock.

Three pillars, all keyed on the same virtual-clock seconds every serving
layer already runs on (engine batching, cluster event loop, sharded
fabric, hoststore swap model):

  * `obs.trace`       — `Tracer`: nestable spans + instant/counter events
                        per (board, lane) track, exported as Chrome
                        trace-event JSON loadable in Perfetto; and
                        `host_span`, the wall-clock span of the real-time
                        request path (a profiler annotation on the device
                        trace's clock plus a duration histogram).
  * `obs.metrics`     — `MetricsRegistry`: process-local named counters /
                        gauges / histograms with labels, snapshot-able as
                        a plain dict; the stack's meters publish here.
  * `obs.attribution` — per-query lifecycle records decomposing each
                        query's latency into queue_wait + batch_wait +
                        compute + link_stall + swap_stall + remesh_barrier
                        (components sum to the latency), aggregated into a
                        `BlameReport` (p99 tail vs median decomposition).

`obs.serialize` is the shared report-JSON path (`to_jsonable`) the
FleetReport / SLAReport / PlanReport `asdict()`/`to_json()` methods and
the launchers' `--report-json` flag ride.
"""
from repro.obs.attribution import (COMPONENTS, AttributionLog, BlameReport,
                                   QueryRecord, interval_overlap_s)
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.obs.serialize import report_asdict, report_to_json, to_jsonable
from repro.obs.trace import Tracer, host_span

__all__ = [
    "AttributionLog",
    "BlameReport",
    "COMPONENTS",
    "MetricsRegistry",
    "QueryRecord",
    "Tracer",
    "default_registry",
    "host_span",
    "interval_overlap_s",
    "report_asdict",
    "report_to_json",
    "to_jsonable",
]
