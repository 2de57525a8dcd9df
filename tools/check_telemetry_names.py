#!/usr/bin/env python
"""Lint: every telemetry metric name must be in the checked-in registry.

A typo'd metric name (``tel.gauge("serve.ttft_m", ...)``) never errors — it
silently mints a second series, and every consumer keyed on the real name
(monitor panel, SSTATS percentile, analyze_trace attribution) quietly loses
data. This lint makes the name set closed:

* ``maggy_tpu/telemetry/metrics.py`` is the registry — per-kind frozensets
  (``GAUGES``/``COUNTERS``/``HISTOGRAMS``/``EVENTS``/``SPANS``) plus
  ``DYNAMIC_PREFIXES`` for the few f-string names whose tail is a bounded
  runtime enum (request terminal states, RPC verbs).
* This tool AST-walks ``maggy_tpu/`` for ``.gauge(`` / ``.count(`` /
  ``.histogram(`` / ``.event(`` / ``.span(`` / ``.record_span(`` calls on telemetry-ish receivers (any name
  in the receiver chain containing ``tel`` — ``tel``, ``telemetry``,
  ``self.telemetry``, ``telemetry.get()`` — so ``str.count`` is never
  flagged) and checks:
  - a literal string name must be in the registry set for its kind;
  - an f-string name's leading literal must match a dynamic prefix;
  - anything else (a plain variable) is skipped — it cannot be checked
    statically, and the codebase passes literals everywhere that matters.
* Every registered name must carry a unit (``metrics.UNITS``, values from
  ``metrics.VALID_UNITS``) — so consumers (monitor, metrics_query, docs)
  never guess at scaling.
* ``maggy_tpu/telemetry/alerts.py`` is loaded the same way and its rule
  registry validated: unique ``alert.``-prefixed names, known
  kind/severity/scope, referenced metrics registered. Any *other*
  ``"alert.*"`` string literal in the tree must name a registered rule or
  transition event — a typo'd rule name must not mint a phantom alert.

Usage: ``python tools/check_telemetry_names.py [root]`` — exits nonzero
listing violations. Built on the shared ``tools/analysis`` framework
(docs/static_analysis.md); wired into the tier-1 run via
``tests/test_tracing.py``, beside the host-sync, exception-hygiene,
bare-print, and docs-nav lints.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import List, Tuple

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
if _TOOLS_DIR not in sys.path:
    sys.path.insert(0, _TOOLS_DIR)

from analysis import (  # noqa: E402
    load_module_from_path,
    report,
    repo_root,
    walk_sources,
)

# recorder method -> the registry kind its first argument names
TELEMETRY_METHODS = {
    "gauge": "gauge", "count": "count", "histogram": "histogram", "event": "event",
    "span": "span", "record_span": "span",
}


def load_registry(repo: str):
    """Load metrics.py by path (no package import — it must stay stdlib-only)."""
    return load_module_from_path(
        "maggy_tpu_metrics_registry",
        os.path.join(repo, "maggy_tpu", "telemetry", "metrics.py"),
    )


def load_alerts(repo: str):
    """Load the alert-rule registry by path (stdlib-only, like metrics.py)."""
    return load_module_from_path(
        "maggy_tpu_alerts_registry",
        os.path.join(repo, "maggy_tpu", "telemetry", "alerts.py"),
    )


def check_units(registry) -> List[str]:
    """Every registered name carries a known unit; no stale unit entries."""
    out: List[str] = []
    units = getattr(registry, "UNITS", None)
    valid = getattr(registry, "VALID_UNITS", None)
    if units is None or valid is None:
        return ["metrics.py must define UNITS and VALID_UNITS"]
    for name in sorted(registry.ALL):
        unit = units.get(name)
        if unit is None:
            out.append(f"{name}: no unit — add it to UNITS in telemetry/metrics.py")
        elif unit not in valid:
            out.append(f"{name}: unknown unit {unit!r} (valid: {sorted(valid)})")
    for name in sorted(units):
        if name not in registry.ALL:
            out.append(f"UNITS entry {name!r} is not a registered metric")
    return out


def check_alert_registry(alerts, registry) -> List[str]:
    """Structural validation of the checked-in alert rules."""
    out: List[str] = []
    rules = getattr(alerts, "RULES", ())
    if len({r.name for r in rules}) != len(rules):
        out.append("duplicate rule names in alerts.RULES")
    for r in rules:
        where = f"alerts.RULES[{r.name!r}]"
        if not r.name.startswith("alert."):
            out.append(f"{where}: name must start with 'alert.'")
        if r.kind not in alerts.KINDS:
            out.append(f"{where}: unknown kind {r.kind!r}")
        if r.severity not in alerts.SEVERITIES:
            out.append(f"{where}: unknown severity {r.severity!r}")
        if r.scope not in alerts.SCOPES:
            out.append(f"{where}: unknown scope {r.scope!r}")
        if not r.summary:
            out.append(f"{where}: empty summary")
        if r.kind == "threshold" and not r.metric:
            out.append(f"{where}: threshold rule needs a metric")
        if r.kind == "burn_rate":
            counter_pair = bool(r.ok_metric) and bool(r.miss_metric)
            hist_src = bool(r.metric) and r.slo_ms is not None
            if not (counter_pair or hist_src):
                out.append(
                    f"{where}: burn_rate rule needs ok/miss counters or metric+slo_ms"
                )
            if not r.windows:
                out.append(f"{where}: burn_rate rule needs windows")
            if not 0.0 < r.objective < 1.0:
                out.append(f"{where}: objective must be in (0, 1)")
        for m in r.metrics():
            if m not in registry.ALL and not any(
                m.startswith(p) for p in registry.DYNAMIC_PREFIXES
            ):
                out.append(f"{where}: references unregistered metric {m!r}")
    for ev in (alerts.ALERT_FIRING, alerts.ALERT_RESOLVED):
        if ev not in registry.EVENTS:
            out.append(f"transition event {ev!r} missing from metrics.EVENTS")
    return out


# the capacity-alerting contract (docs/observability.md "Capacity"): these
# rules must exist with exactly these series wirings. They are profcap's
# default watch list — deleting or re-pointing one silently disarms
# alert-triggered profile capture, so the wiring is pinned here.
CAPACITY_RULES = {
    "alert.hbm_headroom": {
        "kind": "burn_rate",
        "ok_metric": "mem.headroom_ok",
        "miss_metric": "mem.headroom_miss",
    },
    "alert.fragmentation": {
        "kind": "threshold",
        "metric": "serve.fragmentation",
    },
}


def check_capacity_rules(alerts) -> List[str]:
    """The two capacity rules exist and read the series the exporters
    actually write (MemoryLedger's counter pair, the scheduler tick's
    fragmentation gauge)."""
    out: List[str] = []
    by_name = {r.name: r for r in getattr(alerts, "RULES", ())}
    for name, want in CAPACITY_RULES.items():
        r = by_name.get(name)
        if r is None:
            out.append(f"capacity rule {name!r} missing from alerts.RULES")
            continue
        for field, expect in want.items():
            got = getattr(r, field, None)
            if got != expect:
                out.append(
                    f"alerts.RULES[{name!r}]: {field}={got!r}, expected {expect!r}"
                )
        if want.get("kind") == "burn_rate" and len(getattr(r, "windows", ()) or ()) < 2:
            out.append(
                f"alerts.RULES[{name!r}]: multi-window burn rule needs >= 2 windows"
            )
    return out


# the host-DRAM tier observability contract (docs/serving.md "Host-DRAM
# page tier"): the tier.* series the scheduler tick and engine emit must
# stay registered under exactly these kinds with these units — consumers
# (monitor tier line, fleet capacity aggregation) key
# on them, and a silent re-kind (gauge -> counter) breaks every one.
TIER_SERIES = {
    "tier.host_pages_free": ("gauge", "count"),
    "tier.host_pages_total": ("gauge", "count"),
    "tier.host_bytes": ("gauge", "bytes"),
    "tier.resident_packs": ("gauge", "count"),
    "tier.spills": ("count", "count"),
    "tier.fills": ("count", "count"),
    "tier.spilled_pages": ("count", "count"),
    "tier.filled_pages": ("count", "count"),
    "tier.prefix_spills": ("count", "count"),
    "tier.prefix_fills": ("count", "count"),
    "tier.host_evictions": ("count", "count"),
    "tier.pressure_spills": ("count", "count"),
    "tier.affinity_hits": ("count", "count"),
    "tier.affinity_misses": ("count", "count"),
    "tier.swap_in_ms": ("histogram", "ms"),
    "tier.spill_ms": ("histogram", "ms"),
}


def check_tier_series(registry) -> List[str]:
    """Every pinned tier.* series is registered under the expected kind
    and carries the expected unit."""
    out: List[str] = []
    units = getattr(registry, "UNITS", {})
    for name, (kind, unit) in sorted(TIER_SERIES.items()):
        allowed = registry.BY_KIND.get(kind, frozenset())
        if name not in allowed:
            out.append(
                f"tier series {name!r} must be registered as a {kind} "
                "in telemetry/metrics.py"
            )
            continue
        got = units.get(name)
        if got != unit:
            out.append(
                f"tier series {name!r}: unit {got!r}, expected {unit!r}"
            )
    return out


# the autoscaler observability contract (docs/fleet.md "Autoscaling"): the
# fleet.* capacity-loop series must stay registered under exactly these
# kinds with these units — the monitor autoscale line and
# alert.fleet_at_capacity key on them.
AUTOSCALE_SERIES = {
    "fleet.replicas": ("gauge", "count"),
    "fleet.draining": ("gauge", "count"),
    "fleet.at_capacity": ("gauge", "count"),
    "fleet.scale_events": ("count", "count"),
    "fleet.drain_ms": ("histogram", "ms"),
}

# the autoscaler's decision journal: every fleet.scale.* milestone must be
# a registered event — tests and ops tooling replay scale decisions from
# these names, so the set is pinned closed here
AUTOSCALE_EVENTS = (
    "fleet.scale.up",
    "fleet.scale.down",
    "fleet.scale.admitted",
    "fleet.scale.retired",
    "fleet.scale.committed",
    "fleet.scale.rollback",
    "fleet.scale.guard_extended",
    "fleet.scale.blocked",
)


def check_autoscale_series(registry, alerts) -> List[str]:
    """Every pinned autoscale series/event is registered under the
    expected kind, and the at-capacity alert reads the pinned gauge."""
    out: List[str] = []
    units = getattr(registry, "UNITS", {})
    for name, (kind, unit) in sorted(AUTOSCALE_SERIES.items()):
        allowed = registry.BY_KIND.get(kind, frozenset())
        if name not in allowed:
            out.append(
                f"autoscale series {name!r} must be registered as a {kind} "
                "in telemetry/metrics.py"
            )
            continue
        got = units.get(name)
        if got != unit:
            out.append(
                f"autoscale series {name!r}: unit {got!r}, expected {unit!r}"
            )
    for name in AUTOSCALE_EVENTS:
        if name not in registry.EVENTS:
            out.append(
                f"autoscale journal event {name!r} missing from metrics.EVENTS"
            )
    rule = {r.name: r for r in getattr(alerts, "RULES", ())}.get(
        "alert.fleet_at_capacity"
    )
    if rule is None:
        out.append("rule 'alert.fleet_at_capacity' missing from alerts.RULES")
    elif rule.kind != "threshold" or rule.metric != "fleet.at_capacity":
        out.append(
            "alerts.RULES['alert.fleet_at_capacity'] must be a threshold "
            "rule over the 'fleet.at_capacity' gauge"
        )
    return out


def _receiver_is_telemetry(expr: ast.AST) -> bool:
    """True when the call receiver plausibly is a telemetry recorder: some
    identifier in its chain contains 'tel'. Keeps ``"abc".count("a")`` and
    ``mylist.count(x)`` out of scope."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and "tel" in node.id.lower():
            return True
        if isinstance(node, ast.Attribute) and "tel" in node.attr.lower():
            return True
    return False


def check_source(source: str, path: str, registry, alert_names=None) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    tree = ast.parse(source, filename=path)
    for node in ast.walk(tree):
        if (
            alert_names is not None
            and isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and node.value.startswith("alert.")
            and node.value != "alert."  # the bare prefix (strip/match code)
            and node.value not in alert_names
        ):
            out.append(
                (
                    node.lineno,
                    f"{node.value!r} is not a registered alert rule or "
                    "transition event — add it to telemetry/alerts.py RULES "
                    "or fix the typo",
                )
            )
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not isinstance(fn, ast.Attribute) or fn.attr not in TELEMETRY_METHODS:
            continue
        if not _receiver_is_telemetry(fn.value):
            continue
        if not node.args:
            continue
        arg = node.args[0]
        allowed = registry.BY_KIND[TELEMETRY_METHODS[fn.attr]]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
            if name not in allowed:
                hint = (
                    " (registered under another kind)"
                    if name in registry.ALL
                    else ""
                )
                out.append(
                    (
                        node.lineno,
                        f"{fn.attr}({name!r}) not in the metric registry"
                        f"{hint} — add it to telemetry/metrics.py or fix the typo",
                    )
                )
        elif isinstance(arg, ast.JoinedStr):
            lead = ""
            if arg.values and isinstance(arg.values[0], ast.Constant):
                lead = str(arg.values[0].value)
            if not any(lead.startswith(p) for p in registry.DYNAMIC_PREFIXES):
                out.append(
                    (
                        node.lineno,
                        f"{fn.attr}(f\"{lead}...\") has no registered dynamic "
                        "prefix — add one to DYNAMIC_PREFIXES or use a literal",
                    )
                )
        # plain variables: statically uncheckable, skipped
    return out


def check_tree(root: str, registry, alert_names=None) -> List[Tuple[str, int, str]]:
    return walk_sources(
        root, lambda source, path: check_source(source, path, registry, alert_names)
    )


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    repo = repo_root()
    root = args[0] if args else os.path.join(repo, "maggy_tpu")
    registry = load_registry(repo)
    alerts = load_alerts(repo)
    violations: List[Tuple[str, int, str]] = []
    reg_path = os.path.join(repo, "maggy_tpu", "telemetry", "metrics.py")
    violations.extend((reg_path, 0, what) for what in check_units(registry))
    violations.extend(
        (reg_path, 0, what) for what in check_tier_series(registry)
    )
    alerts_path = os.path.join(repo, "maggy_tpu", "telemetry", "alerts.py")
    violations.extend(
        (alerts_path, 0, what) for what in check_alert_registry(alerts, registry)
    )
    violations.extend(
        (alerts_path, 0, what) for what in check_capacity_rules(alerts)
    )
    violations.extend(
        (reg_path, 0, what)
        for what in check_autoscale_series(registry, alerts)
    )
    alert_names = {r.name for r in alerts.RULES} | {
        alerts.ALERT_FIRING,
        alerts.ALERT_RESOLVED,
    }
    violations.extend(check_tree(root, registry, alert_names))
    return report(violations)


if __name__ == "__main__":
    sys.exit(main())
