"""The rule engine behind ``kernel_trust.json``
(``observability/regression.py``): direction, tolerance, the strict
limit, missing values, dotted fields, document scope, rule serde and the
report ``kerneldiff --baseline`` prints.  The committed rules themselves
are held against the committed document in ``tests/test_numerics.py``."""

import copy

import pytest

from deeplearning4j_tpu.observability import regression as reg

pytestmark = pytest.mark.profiling


def doc(**entries):
    return {"all": [{"metric": m, **(v if isinstance(v, dict)
                                     else {"value": v})}
                    for m, v in entries.items()]}


def summary(failing):
    return {"all": [], "summary": {"failing_configs": failing}}


BASE = doc(**{"Throughput (cfg)": 100.0, "Error (cfg)": 10.0})
HIGHER_20 = reg.Rule("Throughput", tolerance=0.2)
LOWER_50 = reg.Rule("Error", direction=reg.LOWER, tolerance=0.5)
FAILING = reg.Rule("Failing configs", scope="doc",
                   field="summary.failing_configs", direction=reg.LOWER,
                   tolerance=0.0)
NESTED = doc(**{"Kernel (cfg)": {"value": 1.0,
                                 "variants": {"fast": {"err": 0.01}}}})
NESTED_WORSE = doc(**{"Kernel (cfg)": {"value": 1.0,
                                       "variants": {"fast": {"err": 0.1}}}})

# (id, baseline, fresh, rule, status, exit code)
CASES = [
    ("higher_drop_past_tolerance", BASE, doc(**{"Throughput (cfg)": 50.0}),
     HIGHER_20, "regressed", 1),
    ("higher_drop_within_tolerance", BASE,
     doc(**{"Throughput (cfg)": 85.0}), HIGHER_20, "ok", 0),
    ("higher_exactly_at_the_limit", BASE,
     doc(**{"Throughput (cfg)": 80.0}), HIGHER_20, "ok", 0),
    ("higher_gain_is_improved", BASE, doc(**{"Throughput (cfg)": 150.0}),
     HIGHER_20, "improved", 0),
    ("lower_doubling_past_tolerance", BASE, doc(**{"Error (cfg)": 20.0}),
     LOWER_50, "regressed", 1),
    ("lower_rise_within_tolerance", BASE, doc(**{"Error (cfg)": 12.0}),
     LOWER_50, "ok", 0),
    ("zero_baseline_zero_tolerance", summary(0), summary(2), FAILING,
     "regressed", 1),
    ("missing_required", BASE, {"all": []}, reg.Rule("Throughput"),
     "regressed", 1),
    ("missing_optional", BASE, {"all": []},
     reg.Rule("Throughput", required=False), "missing", 0),
    ("no_baseline_is_skipped", {"all": []}, BASE, reg.Rule("Throughput"),
     "no_baseline", 0),
    ("dotted_field", NESTED, NESTED_WORSE,
     reg.Rule("Kernel", field="variants.fast.err", direction=reg.LOWER,
              tolerance=0.2), "regressed", 1),
    ("doc_scope_worse", summary(4), summary(8), FAILING, "regressed", 1),
    ("doc_scope_better", summary(4), summary(1), FAILING, "improved", 0),
]


@pytest.mark.parametrize("baseline,fresh,rule,status,exit_code",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_compare(baseline, fresh, rule, status, exit_code):
    rep = reg.compare(baseline, fresh, [rule])
    assert [v.status for v in rep.verdicts] == [status]
    assert rep.exit_code == exit_code
    assert len(rep.regressions) == exit_code


@pytest.mark.parametrize("bad", [
    {"field": "value"},
    {"metric": "x", "direction": "sideways"},
    {"metric": "x", "bogus": 1},
    {"metric": "x", "tolerance": -0.1},
    {"metric": "x", "scope": "entry"},
], ids=["no_metric", "direction", "unknown_key", "tolerance", "scope"])
def test_bad_rule_is_refused(bad):
    with pytest.raises(ValueError):
        reg.Rule.from_dict(bad)


def test_direction_and_tolerance():
    base = {"all": [{"metric": "Tput (x)", "value": 100.0},
                    {"metric": "Lat (x)", "value": 10.0}]}
    worse = {"all": [{"metric": "Tput (x)", "value": 70.0},
                     {"metric": "Lat (x)", "value": 13.0}]}
    rules = [reg.Rule("Tput", tolerance=0.2),
             reg.Rule("Lat", direction=reg.LOWER, tolerance=0.2)]
    rep = reg.compare(base, worse, rules)
    assert [v.status for v in rep.verdicts] == ["regressed", "regressed"]
    assert rep.exit_code == 1
    within = {"all": [{"metric": "Tput (x)", "value": 85.0},
                      {"metric": "Lat (x)", "value": 11.0}]}
    assert reg.compare(base, within, rules).exit_code == 0


def test_missing_and_no_baseline():
    base = {"all": [{"metric": "Tput (x)", "value": 100.0}]}
    rep = reg.compare(base, {"all": []}, [reg.Rule("Tput")])
    assert rep.verdicts[0].status == "regressed"   # required by default
    rep = reg.compare(base, {"all": []},
                      [reg.Rule("Tput", required=False)])
    assert rep.verdicts[0].status == "missing" and rep.exit_code == 0
    rep = reg.compare({"all": []}, base, [reg.Rule("Tput")])
    assert rep.verdicts[0].status == "no_baseline" and rep.exit_code == 0


def test_dotted_field_and_rule_roundtrip():
    base = {"all": [{"metric": "D (x)", "value": 1.0,
                     "variants": {"v": {"tps": 50.0}}}]}
    fresh = copy.deepcopy(base)
    fresh["all"][0]["variants"]["v"]["tps"] = 10.0
    rule = reg.Rule("D", field="variants.v.tps", tolerance=0.3)
    assert reg.compare(base, fresh, [rule]).exit_code == 1
    assert reg.Rule.from_dict(rule.to_dict()).to_dict() == rule.to_dict()
    with pytest.raises(ValueError):
        reg.Rule("x", direction="sideways")
    with pytest.raises(ValueError):
        reg.Rule.from_dict({"metric": "x", "bogus": 1})


def test_report_text_and_dict():
    """What ``kerneldiff --baseline`` prints and returns: one line a rule
    naming the regressed one, a PASS / FAIL total, and the same as a
    JSON-safe dict."""
    rules = [HIGHER_20, LOWER_50]
    bad = reg.compare(BASE, doc(**{"Throughput (cfg)": 50.0,
                                   "Error (cfg)": 10.0}), rules)
    text = bad.format()
    assert "REGRESSED Throughput :: value" in text
    assert text.splitlines()[-1] == \
        "FAIL: 1 regression(s) in 2 checked rule(s)"
    d = bad.to_dict()
    assert (d["regressed"], d["checked"]) == (1, 2)
    assert d["verdicts"][0]["limit"] == 80.0
    assert reg.compare(BASE, BASE, rules).format().splitlines()[-1] == \
        "PASS: 0 regression(s) in 2 checked rule(s)"
