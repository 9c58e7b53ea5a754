#!/usr/bin/env python
"""One command for every fast source-level CI gate.

Runs, in order:

1. ``dl4jlint`` — the full static-analysis suite (all six rules,
   including metrics-docs) against its committed ratcheting baseline;
2. ``check_metrics_docs`` — the standalone shim, proving the
   backwards-compatible entry point still answers (it shares the
   metrics-docs rule with dl4jlint, so this is a wiring check);
3. ``fleet schema self-test`` — the fleet telemetry snapshot's
   serialize → merge → re-export round trip must be bit-stable
   (``observability.fleet.schema_roundtrip_selftest``);
4. ``kernel-trust registry`` — the committed ``kernel_trust.json`` and
   the kerneldiff sweep registry must list the same kernels in both
   directions, so no fused kernel can merge without sweep evidence and
   no stale trust entry can outlive its kernel
   (``kerneldiff --check-registry``);
5. ``fleet placement self-test`` — the router's placement policy
   simulated end to end with no jax and no package imports
   (``fleet/placement.py`` is loaded BY FILE PATH): deterministic seeded
   ties, affinity beating the seeded-random control on hit rate,
   version-tag shadow invalidation, drain/stale/dead exclusion,
   canary-split fractions, session pins.

All five run in a few seconds with no device work — this is the
pre-test gate: run it before the pytest tiers and fail fast on lint
debt, a fleet wire-schema drift, stale kernel-trust evidence, or a
placement policy regression.

Usage::

    python scripts/ci_checks.py            # run everything
    python scripts/ci_checks.py --list     # show what would run

Exit codes: 0 all gates passed, 1 any gate failed, 2 usage/IO error —
the same contract as each individual gate.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
from typing import List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHECKS: List[Tuple[str, List[str]]] = [
    ("dl4jlint", [sys.executable, "-m", "scripts.dl4jlint"]),
    ("metrics-docs shim",
     [sys.executable, os.path.join(REPO, "scripts",
                                   "check_metrics_docs.py")]),
    ("fleet schema self-test",
     [sys.executable, "-c",
      "import sys; "
      "from deeplearning4j_tpu.observability.fleet import "
      "schema_roundtrip_selftest; "
      "sys.exit(schema_roundtrip_selftest(verbose=True))"]),
    ("kernel-trust registry",
     [sys.executable, "-m",
      "deeplearning4j_tpu.observability.kerneldiff",
      "--check-registry", os.path.join(REPO, "kernel_trust.json")]),
    ("fleet placement self-test",
     [sys.executable, "-c",
      "import importlib.util, sys; "
      "spec = importlib.util.spec_from_file_location("
      "'fleet_placement', "
      f"{os.path.join(REPO, 'deeplearning4j_tpu', 'fleet', 'placement.py')!r}); "
      "m = importlib.util.module_from_spec(spec); "
      "spec.loader.exec_module(m); "
      "sys.exit(m.placement_selftest(verbose=True))"]),
]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--list", action="store_true",
                    help="print the gate commands and exit")
    args = ap.parse_args(argv)
    if args.list:
        for name, cmd in CHECKS:
            print(f"{name}: {' '.join(cmd)}")
        return 0

    failed: List[str] = []
    for name, cmd in CHECKS:
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, cwd=REPO)
        dt = time.perf_counter() - t0
        status = "ok" if proc.returncode == 0 else f"FAILED ({proc.returncode})"
        print(f"ci_checks: {name}: {status} in {dt:.2f}s", file=sys.stderr)
        if proc.returncode == 2:
            print(f"ci_checks: {name} reported a usage/IO error — "
                  f"aborting", file=sys.stderr)
            return 2
        if proc.returncode != 0:
            failed.append(name)
    if failed:
        print(f"ci_checks: {len(failed)}/{len(CHECKS)} gates failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    print(f"ci_checks: all {len(CHECKS)} gates passed", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
