"""Performance observatory: cost model (FLOPs/bytes per jitted root,
live `perf/mfu` / `perf/membw_util` / `perf/flops_per_step` gauges),
flight-recorder overlap analyzer (`report.py`), and — on the tooling
side — `tools/perfgate.py`, the BENCH_HISTORY.jsonl regression gate.

See docs/OBSERVABILITY.md "Performance observatory" for the gauge
table, report anatomy, and the perfgate workflow.
"""

from torched_impala_tpu.perf.costmodel import (
    DEVICE_PEAKS,
    CostModel,
    DevicePeaks,
    RootCost,
    device_peaks,
    extract_compiled_cost,
    param_count,
    static_flops_estimate,
)
from torched_impala_tpu.perf.report import (
    GAP_CATEGORIES,
    analyze_records,
    categorize_span,
    generate_report,
    install_sigusr2_report,
    measure,
    render_report,
    subtract,
    union,
    write_report,
)

__all__ = [
    "DEVICE_PEAKS",
    "CostModel",
    "DevicePeaks",
    "RootCost",
    "device_peaks",
    "extract_compiled_cost",
    "param_count",
    "static_flops_estimate",
    "GAP_CATEGORIES",
    "analyze_records",
    "categorize_span",
    "generate_report",
    "install_sigusr2_report",
    "measure",
    "render_report",
    "subtract",
    "union",
    "write_report",
]
