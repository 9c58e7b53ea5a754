"""The comparison behind ``kernel_trust.json``: per-metric direction and
tolerance rules over two JSON documents.

``python -m deeplearning4j_tpu.observability.kerneldiff --baseline
kernel_trust.json`` sweeps every kernel against its reference and holds
the fresh report to the committed one with ``KERNEL_TRUST_RULES``: a rule
says which field of which entry matters, which DIRECTION is good, and how
much relative slack the value gets before a change counts as a
regression.  The values are error bounds and counts from a deterministic
sweep, never timings: speed is measured by ``python3 -m benchmark.run``
on the chip and judged by the driver (``PERF.md``).

Rule addressing: entries live in ``doc["all"]``, each with a ``metric``
name like ``"Kernel max rel error (flash_attention)"``; rules match on
the PREFIX.  ``field`` is a dotted path inside the entry (``"value"``,
``"variants.fast.tps"``).  With ``scope="doc"`` the rule skips the entry
lookup and resolves ``field`` from the DOCUMENT root instead — how
``summary.failing_configs`` is addressed (the ``metric`` string is then
only the display name).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

HIGHER = "higher"   # bigger is better
LOWER = "lower"     # smaller is better (an error bound, a failure count)


class Rule:
    """One metric's regression policy."""

    __slots__ = ("metric", "field", "direction", "tolerance", "required",
                 "scope")

    def __init__(self, metric: str, field: str = "value",
                 direction: str = HIGHER, tolerance: float = 0.15,
                 required: bool = True, scope: str = "all"):
        if direction not in (HIGHER, LOWER):
            raise ValueError(
                f"direction must be {HIGHER!r} or {LOWER!r}, got {direction!r}")
        if tolerance < 0:
            raise ValueError(f"tolerance must be >= 0, got {tolerance}")
        if scope not in ("all", "doc"):
            raise ValueError(f"scope must be 'all' or 'doc', got {scope!r}")
        self.metric = str(metric)
        self.field = str(field)
        self.direction = direction
        self.tolerance = float(tolerance)
        self.required = bool(required)
        self.scope = scope

    @property
    def key(self) -> str:
        return f"{self.metric} :: {self.field}"

    def to_dict(self) -> Dict[str, Any]:
        return {"metric": self.metric, "field": self.field,
                "direction": self.direction, "tolerance": self.tolerance,
                "required": self.required, "scope": self.scope}

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "Rule":
        unknown = set(d) - {"metric", "field", "direction", "tolerance",
                            "required", "scope"}
        if unknown:
            raise ValueError(f"unknown rule keys: {sorted(unknown)}")
        if "metric" not in d:
            raise ValueError(f"rule needs a 'metric': {d!r}")
        return Rule(d["metric"], d.get("field", "value"),
                    d.get("direction", HIGHER), d.get("tolerance", 0.15),
                    d.get("required", True), d.get("scope", "all"))


# The committed policy over kernel_trust.json (observability.kerneldiff
# sweeps; ``python -m ...kerneldiff --baseline kernel_trust.json``).
# Worst-config max-rel-error per kernel: direction=lower with a 1.0
# tolerance — the CPU-interpret sweep is deterministic, so the slack is
# for dtype-budget headroom, not jitter; a doubling of any kernel's
# divergence regresses.  The doc-scope rule pins "no config anywhere
# fails its budget" exactly (baseline 0, tolerance 0).
KERNEL_TRUST_RULES: List[Rule] = [
    Rule("Kernel max rel error (flash_attention)", direction=LOWER,
         tolerance=1.0),
    Rule("Kernel max rel error (dot_product_attention)", direction=LOWER,
         tolerance=1.0),
    Rule("Kernel max rel error (gather_pages)", direction=LOWER,
         tolerance=0.0),
    Rule("Kernel max rel error (paged_attention)", direction=LOWER,
         tolerance=1.0),
    # the fused decode kernel (ISSUE 19) sweeps BOTH impls behind the
    # seam (lax fallback + interpreted Pallas) in one flat comparison;
    # the train-step epilogue likewise covers residual/prologue/norm-only
    # variants under one entry
    Rule("Kernel max rel error (fused_paged_attention)", direction=LOWER,
         tolerance=1.0),
    Rule("Kernel max rel error (fused_dropout_residual_norm)",
         direction=LOWER, tolerance=1.0),
    Rule("Kernel max rel error (pallas_lrn)", direction=LOWER,
         tolerance=1.0, required=False),
    Rule("Kernel max rel error (pallas_bn_inference)", direction=LOWER,
         tolerance=1.0, required=False),
    Rule("Kernel max rel error (pallas_bn_training)", direction=LOWER,
         tolerance=1.0, required=False),
    Rule("Kernel trust failing configs", scope="doc",
         field="summary.failing_configs", direction=LOWER, tolerance=0.0),
]


# ------------------------------------------------------------- extraction
def _find_entry(doc: Dict[str, Any], metric_prefix: str) -> Optional[Dict]:
    for entry in doc.get("all", []) or []:
        if str(entry.get("metric", "")).startswith(metric_prefix):
            return entry
    return None


def _get_field(entry: Dict[str, Any], dotted: str) -> Optional[float]:
    cur: Any = entry
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    if isinstance(cur, bool) or not isinstance(cur, (int, float)):
        return None
    return float(cur)


def extract(doc: Dict[str, Any], rule: Rule) -> Optional[float]:
    if rule.scope == "doc":
        return _get_field(doc, rule.field)
    entry = _find_entry(doc, rule.metric)
    if entry is None:
        return None
    return _get_field(entry, rule.field)


# -------------------------------------------------------------- comparison
class Verdict:
    """One rule's outcome: ``status`` in {"ok", "improved", "regressed",
    "missing", "no_baseline"}."""

    __slots__ = ("rule", "status", "baseline", "fresh", "limit", "detail")

    def __init__(self, rule: Rule, status: str, baseline, fresh, limit,
                 detail: str):
        self.rule = rule
        self.status = status
        self.baseline = baseline
        self.fresh = fresh
        self.limit = limit
        self.detail = detail

    def to_dict(self) -> Dict[str, Any]:
        return {"metric": self.rule.metric, "field": self.rule.field,
                "direction": self.rule.direction,
                "tolerance": self.rule.tolerance, "status": self.status,
                "baseline": self.baseline, "fresh": self.fresh,
                "limit": self.limit, "detail": self.detail}


class Report:
    def __init__(self, verdicts: List[Verdict]):
        self.verdicts = verdicts

    @property
    def regressions(self) -> List[Verdict]:
        return [v for v in self.verdicts if v.status == "regressed"]

    @property
    def exit_code(self) -> int:
        return 1 if self.regressions else 0

    def to_dict(self) -> Dict[str, Any]:
        return {"regressed": len(self.regressions),
                "checked": len(self.verdicts),
                "verdicts": [v.to_dict() for v in self.verdicts]}

    def format(self) -> str:
        lines = []
        for v in self.verdicts:
            mark = {"ok": "ok       ", "improved": "improved ",
                    "regressed": "REGRESSED", "missing": "missing  ",
                    "no_baseline": "skipped  "}[v.status]
            lines.append(f"{mark} {v.rule.key}: {v.detail}")
        n = len(self.regressions)
        lines.append(f"{'FAIL' if n else 'PASS'}: {n} regression(s) in "
                     f"{len(self.verdicts)} checked rule(s)")
        return "\n".join(lines)


def compare(baseline: Dict[str, Any], fresh: Dict[str, Any],
            rules: List[Rule]) -> Report:
    """Evaluate every rule: a fresh value past ``baseline * (1 ± tol)``
    in the BAD direction regresses; a missing fresh value regresses when
    the rule is ``required``; a MISSING baseline skips the rule
    (``no_baseline`` — there is nothing to hold the line against).  A
    zero baseline is enforced, not skipped: with ``direction=lower`` and
    ``tolerance=0`` it means "any increase regresses" — the
    ``summary.failing_configs`` rule depends on exactly that."""
    verdicts: List[Verdict] = []
    for rule in rules:
        base = extract(baseline, rule)
        new = extract(fresh, rule)
        if base is None:
            verdicts.append(Verdict(rule, "no_baseline", None, new, None,
                                    "no baseline value"))
            continue
        if new is None:
            status = "regressed" if rule.required else "missing"
            verdicts.append(Verdict(
                rule, status, base, None, None,
                "value missing from fresh run"
                + ("" if rule.required else " (optional)")))
            continue
        if rule.direction == HIGHER:
            limit = base * (1.0 - rule.tolerance)
            regressed = new < limit
            improved = new > base
        else:
            limit = base * (1.0 + rule.tolerance)
            regressed = new > limit
            improved = new < base
        status = ("regressed" if regressed
                  else "improved" if improved else "ok")
        arrow = "<" if rule.direction == HIGHER else ">"
        detail = (f"fresh {new:g} vs baseline {base:g} "
                  f"(fails when {arrow} {limit:g})")
        verdicts.append(Verdict(rule, status, base, new, limit, detail))
    return Report(verdicts)
