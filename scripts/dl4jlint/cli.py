"""dl4jlint driver.

Usage::

    python -m scripts.dl4jlint                    # repo scan vs baseline
    python -m scripts.dl4jlint --update-baseline  # ratchet the debt DOWN
    python -m scripts.dl4jlint path/to/file.py --no-baseline
    python -m scripts.dl4jlint --rules lock-discipline,thread-hygiene
    python -m scripts.dl4jlint --list-rules
    python -m scripts.dl4jlint --json

Exit codes: 0 clean against the baseline, 1 new findings (or a refused
ratchet), 2 usage/IO error.
Stdlib-only, never imports jax; a full-repo run is sub-second.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

from scripts.dl4jlint import baseline as baseline_mod
from scripts.dl4jlint.core import (
    REPO, RunResult, iter_source_files, load_contexts, run_rules,
)
from scripts.dl4jlint.rules import ALL_RULES, get_rules

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")


def run(paths=None, rule_names=()) -> RunResult:
    """Library entry: scan and return the RunResult (no baseline)."""
    files = iter_source_files(paths)
    ctxs, errors = load_contexts(files)
    return run_rules(get_rules(rule_names), ctxs, errors)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="dl4jlint", description=__doc__.split("\n")[0])
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to scan (default: the "
                         "deeplearning4j_tpu package)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule names (default: all)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline JSON path (default: the committed one)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding; exit 1 if any")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline at current counts "
                         "(refuses to grow it)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the full report as JSON")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in ALL_RULES:
            print(f"{r.name:24s} {r.description}")
        return 0

    t0 = time.perf_counter()
    try:
        rule_names = ([n.strip() for n in args.rules.split(",") if n.strip()]
                      if args.rules else ())
        res = run(args.paths or None, rule_names)
    except KeyError as e:
        print(f"error: {e.args[0]}", file=sys.stderr)
        return 2
    for err in res.errors:
        print(f"error: {err}", file=sys.stderr)
    if res.errors:
        return 2

    if args.no_baseline:
        doc = None
        new, stale = list(res.findings), []
    else:
        try:
            doc = (baseline_mod.load(args.baseline)
                   if os.path.exists(args.baseline) else None)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        if args.update_baseline:
            try:
                newdoc = baseline_mod.update(res.findings, doc)
            except baseline_mod.RatchetError as e:
                print(f"dl4jlint: {e}", file=sys.stderr)
                return 1
            baseline_mod.save(args.baseline, newdoc)
            print(f"dl4jlint: baseline "
                  f"{'created' if doc is None else 'ratcheted'} at "
                  f"{len(newdoc['entries'])} entr"
                  f"{'y' if len(newdoc['entries']) == 1 else 'ies'} "
                  f"({sum(e['count'] for e in newdoc['entries'])} accepted "
                  f"findings) -> {os.path.relpath(args.baseline, REPO)}")
            return 0
        new, stale = baseline_mod.compare(
            res.findings, doc if doc is not None else baseline_mod.empty())

    dt = time.perf_counter() - t0
    if args.as_json:
        print(json.dumps({
            "files": res.files, "seconds": round(dt, 3),
            "total_findings": len(res.findings),
            "suppressed": res.suppressed,
            "new": [f.to_dict() for f in new],
            "stale_baseline_keys": [list(k) for k in stale],
        }, indent=1))
    else:
        for f in new:
            print(f.format())
        if stale:
            print(f"dl4jlint: note: {len(stale)} baseline entr"
                  f"{'y has' if len(stale) == 1 else 'ies have'} fewer "
                  f"findings than budgeted — run --update-baseline to "
                  f"bank the progress")
        status = "FAIL" if new else "OK"
        print(f"dl4jlint: {status} — {res.files} files, "
              f"{len(res.findings)} findings "
              f"({len(res.findings) - len(new)} baselined, {len(new)} new, "
              f"{res.suppressed} suppressed) in {dt:.2f}s")
    return 1 if new else 0
