"""telemetry checker: metric AND trace event name grammar.

The former ``tools/check_metric_names.py`` (ISSUE 2 satellite, trace
grammar from ISSUE 4, sub-family rules 3b/3c from ISSUEs 5/6), folded
into the impala-lint framework — same rules, same message bodies, now
emitting :class:`Finding`s so baselining/annotation work uniformly.
The old script remains as a thin CLI shim over this module.

Rules (rule ids in parentheses):

1. every registered metric name — ``.counter("...")`` / ``.gauge`` /
   ``.timer`` / ``.histogram`` / ``.span`` — matches the
   ``<component>/<name>`` slug grammar (``telemetry/name-grammar``);
2. no two call sites register one name with DIFFERENT metric types
   (a ``span`` counts as its backing ``timer``) — a type fork silently
   splits one series into two (``telemetry/type-fork``);
3. literal emitted keys (``"telemetry/..."`` strings,
   ``f"{PREFIX}/..."`` interpolations) carry the same grammar
   (``telemetry/literal-key``);
3b/3c/3d/3e/3f/3g/3h. ``resilience/*``, ``serving/*`` (3g extends the
   set with the fleet_/route_ sub-families), ``replay/*``, ``perf/*``,
   ``control/*`` and (3h, the alerting plane) ``alerts/*`` names use
   their pinned sub-family prefixes (``telemetry/subfamily-prefix``);
3i. aggregated keys — literal keys whose first path segment is a
   ``proc<h>w<w>`` process label (the cross-process fan-in re-prefix,
   telemetry/aggregate.py) — carry a well-formed label AND a
   grammar-clean remainder (``telemetry/agg-prefix``);
3j. ``health/*`` (the training-health plane, telemetry/health.py)
   names use the pinned learning-signal sub-families — clip fractions/
   histogram, entropy, KL, explained variance, grad norms, update
   ratios, PopArt drift, staleness correlation
   (``telemetry/subfamily-prefix``);
4. trace event names — ``.instant`` / ``.begin`` / ``.end`` /
   ``.complete`` — follow the same slug grammar
   (``telemetry/trace-grammar``);
4b. ``serving/*`` TRACE events are a closed set
   (``telemetry/trace-closed-set``).

Rule 3 skips a quoted key that is the NAME argument of a trace call on
the same line: trace events in the ``telemetry/`` component (the
engine's ``telemetry/alert`` instants) are event names, not emitted
metric keys, and rule 4 already validates them.

Static on purpose: runs from tier-1 without initializing jax and sees
dead call sites (a typo'd name in a rarely-taken branch still fails).
The registry/recorder enforce the same grammar at runtime as a backstop
for dynamically-built names this scan cannot see.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Sequence, Tuple

from tools.lint.core import Finding, SourceFile

RULES = {
    "telemetry/name-grammar": "metric name violates <component>/<name>",
    "telemetry/type-fork": "one metric name registered as two types",
    "telemetry/literal-key": "literal emitted key violates the grammar",
    "telemetry/subfamily-prefix": (
        "resilience/*, serving/*, replay/*, perf/*, control/* or "
        "alerts/* name lacks its pinned sub-family prefix"
    ),
    "telemetry/agg-prefix": (
        "aggregated proc<h>w<w>/ key has a malformed label or remainder"
    ),
    "telemetry/trace-grammar": "trace event name violates the grammar",
    "telemetry/trace-closed-set": (
        "serving/* trace event outside the pinned set"
    ),
}

# .counter("pool/restarts") / reg.span('learner/train_step') ...
_REG_CALL = re.compile(
    r"\.(counter|gauge|timer|histogram|span)\(\s*([\"'])([^\"']+)\2"
)
# Flight-recorder event sites; same slug grammar, no type semantics.
_TRACE_CALL = re.compile(
    r"\.(instant|begin|end|complete)\(\s*([\"'])([^\"']+)\2"
)
_LITERAL_KEY = re.compile(r"[\"']telemetry/([a-z0-9_/]+)[\"']")
_PREFIX_KEY = re.compile(r"\{PREFIX\}/([a-z0-9_/]+)")

NAME_RE = re.compile(r"^[a-z][a-z0-9_]*/[a-z][a-z0-9_]*$")

_CANONICAL = {"span": "timer"}

RESILIENCE_PREFIXES = ("checkpoint_", "supervisor_", "chaos_", "recovery_")
# Rule 3g (serving fleet, ISSUE 14) adds the fleet topology/rollout and
# router-decision sub-families to the serving/* set pinned since ISSUE 6.
SERVING_PREFIXES = (
    "request_", "wave_", "shadow_", "client_", "version_", "ring_",
    "fleet_", "route_",
)
# Rule 3d (replay subsystem, ISSUE 9): the replay/* family is pinned to
# the four sub-families docs/OBSERVABILITY.md documents — reuse
# accounting, target-store health, eviction pressure, staleness.
REPLAY_PREFIXES = ("reuse_", "target_", "evict_", "staleness_")
# Rule 3e (performance observatory, ISSUE 10): the perf/* family is
# pinned to the sub-families docs/OBSERVABILITY.md documents —
# model-flop utilization, memory bandwidth, flop counts, gap
# attribution, fused-dispatch fallbacks and gradient all-reduce
# overlap. Checked on `<sub>_` so the bare family names (perf/mfu) pass
# while perf/mfuzzy does not.
PERF_PREFIXES = (
    "mfu_", "membw_", "flops_", "gap_", "fused_", "allreduce_",
)
# Rule 3f (control plane, ISSUE 12): the control/* family is pinned to
# the four sub-families docs/CONTROL.md documents — decision accounting,
# guardrail reverts, objective deltas, live knob values. Checked on
# `<sub>_` like rule 3e so the bare `control/decision` trace event
# passes while control/decisions_made does not.
CONTROL_PREFIXES = ("decision_", "revert_", "objective_", "knob_")
# Rule 3h (SLO burn-rate alerting, ISSUE 17): the alerts/* family is
# pinned to the engine's gauge shapes (telemetry/alerts.py) — firing
# bits, burn rates, and room for slo/window configuration gauges.
ALERTS_PREFIXES = ("burn_", "firing_", "slo_", "window_")
# Rule 3j (training-health plane, ISSUE 19): the health/* family is
# pinned to the learning-signal sub-families docs/OBSERVABILITY.md
# "Training health" tabulates — V-trace clip diagnostics, policy
# entropy, behaviour->learner KL, value explained variance, gradient
# norms, update-to-weight ratios, PopArt drift, replay staleness
# correlation. Prefix-checked (health/clipping fails; health/clip_
# anything passes) like rules 3b-3h.
HEALTH_PREFIXES = (
    "clip_", "entropy_", "kl_", "ev_", "grad_", "update_", "popart_",
    "staleness_",
)
# Rule 3i (cross-process fan-in, ISSUE 17): an aggregated key's first
# segment is a proc<h>w<w> process label (telemetry/aggregate.py
# LABEL_RE) and the rest must itself be a grammar-clean
# <component>/<name> key.
_AGG_LABEL = re.compile(r"^proc\d+w\d+$")
SERVING_TRACE_EVENTS = {
    "serving/request", "serving/wave", "serving/shadow",
    # ISSUE 14 fleet instants: rollout lifecycle + replica failover.
    "serving/rollout", "serving/failover",
}

# These files define the machinery; their docstring examples would read
# as registrations/events.
MACHINERY = {
    os.path.join("torched_impala_tpu", "telemetry", "registry.py").replace(
        os.sep, "/"
    ),
    os.path.join("torched_impala_tpu", "telemetry", "tracing.py").replace(
        os.sep, "/"
    ),
}


def check(files: Sequence[SourceFile]) -> List[Finding]:
    findings: List[Finding] = []
    # name -> (canonical kind, first site)
    seen: Dict[str, Tuple[str, str]] = {}
    for sf in sorted(files, key=lambda s: s.rel):
        if sf.rel in MACHINERY:
            continue
        for lineno, line in enumerate(sf.lines, 1):
            site = f"{sf.rel}:{lineno}"

            def out(rule: str, name: str, message: str) -> None:
                findings.append(
                    Finding(
                        rule=rule,
                        path=sf.rel,
                        line=lineno,
                        message=message,
                        key=f"{sf.rel}::{name}",
                    )
                )

            for kind, _q, name in _REG_CALL.findall(line):
                kind = _CANONICAL.get(kind, kind)
                if not NAME_RE.match(name):
                    out(
                        "telemetry/name-grammar",
                        name,
                        f"{kind} name {name!r} does not match "
                        f"<component>/<name> ({NAME_RE.pattern})",
                    )
                    continue
                if name.startswith("resilience/") and not name.split(
                    "/", 1
                )[1].startswith(RESILIENCE_PREFIXES):
                    out(
                        "telemetry/subfamily-prefix",
                        name,
                        f"resilience metric {name!r} must use a "
                        f"sub-family prefix {RESILIENCE_PREFIXES}",
                    )
                    continue
                if name.startswith("serving/") and not name.split(
                    "/", 1
                )[1].startswith(SERVING_PREFIXES):
                    out(
                        "telemetry/subfamily-prefix",
                        name,
                        f"serving metric {name!r} must use a "
                        f"sub-family prefix {SERVING_PREFIXES}",
                    )
                    continue
                if name.startswith("replay/") and not name.split(
                    "/", 1
                )[1].startswith(REPLAY_PREFIXES):
                    out(
                        "telemetry/subfamily-prefix",
                        name,
                        f"replay metric {name!r} must use a "
                        f"sub-family prefix {REPLAY_PREFIXES}",
                    )
                    continue
                if name.startswith("perf/") and not (
                    name.split("/", 1)[1] + "_"
                ).startswith(PERF_PREFIXES):
                    out(
                        "telemetry/subfamily-prefix",
                        name,
                        f"perf metric {name!r} must use a "
                        f"sub-family prefix {PERF_PREFIXES} (rule 3e)",
                    )
                    continue
                if name.startswith("control/") and not (
                    name.split("/", 1)[1] + "_"
                ).startswith(CONTROL_PREFIXES):
                    out(
                        "telemetry/subfamily-prefix",
                        name,
                        f"control metric {name!r} must use a "
                        f"sub-family prefix {CONTROL_PREFIXES} "
                        f"(rule 3f)",
                    )
                    continue
                if name.startswith("alerts/") and not name.split(
                    "/", 1
                )[1].startswith(ALERTS_PREFIXES):
                    out(
                        "telemetry/subfamily-prefix",
                        name,
                        f"alerts metric {name!r} must use a "
                        f"sub-family prefix {ALERTS_PREFIXES} "
                        f"(rule 3h)",
                    )
                    continue
                if name.startswith("health/") and not name.split(
                    "/", 1
                )[1].startswith(HEALTH_PREFIXES):
                    out(
                        "telemetry/subfamily-prefix",
                        name,
                        f"health metric {name!r} must use a "
                        f"sub-family prefix {HEALTH_PREFIXES} "
                        f"(rule 3j)",
                    )
                    continue
                prev = seen.get(name)
                if prev is None:
                    seen[name] = (kind, site)
                elif prev[0] != kind:
                    out(
                        "telemetry/type-fork",
                        name,
                        f"{name!r} registered as {kind} but {prev[1]} "
                        f"registered it as {prev[0]}",
                    )
            for kind, _q, name in _TRACE_CALL.findall(line):
                if not NAME_RE.match(name):
                    out(
                        "telemetry/trace-grammar",
                        name,
                        f"trace {kind} name {name!r} does not match "
                        f"<component>/<name> ({NAME_RE.pattern})",
                    )
                    continue
                if (
                    name.startswith("serving/")
                    and name not in SERVING_TRACE_EVENTS
                ):
                    out(
                        "telemetry/trace-closed-set",
                        name,
                        f"serving trace event {name!r} is not in the "
                        f"pinned set {sorted(SERVING_TRACE_EVENTS)} "
                        f"(rule 4b)",
                    )
            # Trace-call NAMES on this line: a quoted "telemetry/..."
            # that is the name argument of .instant/.begin/... is an
            # event name (rule 4's job), not an emitted metric key.
            trace_names = {n for _, _, n in _TRACE_CALL.findall(line)}

            def _check_key(path: str, shown: str) -> None:
                head, _, rest = path.partition("/")
                if "/" in rest and head.startswith("proc"):
                    # Aggregated-key shape (rule 3i): proc<h>w<w> label
                    # + a grammar-clean re-prefixed key.
                    if not (
                        _AGG_LABEL.match(head) and NAME_RE.match(rest)
                    ):
                        out(
                            "telemetry/agg-prefix",
                            shown,
                            f"aggregated key '{shown}' must be "
                            f"proc<h>w<w>/<component>/<name> "
                            f"(rule 3i)",
                        )
                    return
                if not NAME_RE.match(path):
                    out(
                        "telemetry/literal-key",
                        shown,
                        f"literal key '{shown}' does not match "
                        f"telemetry/<component>/<name>",
                    )

            for m in _LITERAL_KEY.finditer(line):
                if f"telemetry/{m.group(1)}" in trace_names:
                    continue
                _check_key(m.group(1), f"telemetry/{m.group(1)}")
            for m in _PREFIX_KEY.finditer(line):
                _check_key(m.group(1), f"{{PREFIX}}/{m.group(1)}")
    return findings


def legacy_check(root: str) -> List[str]:
    """The pre-framework surface: scan `root` (torched_impala_tpu/**)
    and return human-readable strings — one per finding,
    ``path:line: message`` — exactly like tools/check_metric_names.py
    always did. The CLI shim and pre-existing tests call this."""
    from tools.lint.core import (
        DEFAULT_ROOTS,
        apply_inline_allows,
        load_files,
    )

    files = load_files(root, DEFAULT_ROOTS)
    findings = apply_inline_allows(files, check(files))
    return [f"{f.path}:{f.line}: {f.message}" for f in findings]
