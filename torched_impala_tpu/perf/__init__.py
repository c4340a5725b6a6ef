"""Performance observatory: cost model (FLOPs/bytes per jitted root,
live `perf/mfu` / `perf/membw_util` / `perf/flops_per_step` gauges)
and the flight-recorder overlap analyzer (`report.py`). What a run
measured on the chip is `benchmark/`'s and PERF.md's to say, not this
package's: its gauges are a running job's estimates.

See docs/OBSERVABILITY.md "Performance observatory" for the gauge
table and the report's anatomy.
"""

from torched_impala_tpu.perf.costmodel import (
    DEVICE_PEAKS,
    CostModel,
    DevicePeaks,
    RootCost,
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
