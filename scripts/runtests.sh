#!/usr/bin/env bash
# Test runner (≙ reference /runtests.sh:33 — the repo-root test entry).
#
#   scripts/runtests.sh            # CPU tier: full suite on the 8-device
#                                  # virtual mesh (no accelerator needed)
#   scripts/runtests.sh dryrun     # multichip sharding dryrun (8 virtual
#                                  # CPU devices)
#   scripts/runtests.sh smoke      # chip_smoke.py: trainer + generation
#                                  # server at d1024 (needs the chip)
#   scripts/runtests.sh tpu        # real-chip tier: tests/test_tpu.py
#   scripts/runtests.sh all        # everything above in order
#
# The first two pin JAX to the CPU; smoke and tpu take whatever platform
# JAX finds and fail without a TPU.  Speed is not a tier of this script:
# python3 -m benchmark.run --workload <cell> ... on the chip (PERF.md).  Each tier is one
# process at a time: a chip belongs to one process.
set -euo pipefail
cd "$(dirname "$0")/.."

tier="${1:-cpu}"

run_cpu()    { JAX_PLATFORMS=cpu python -m pytest tests/ -q; }
run_dryrun() { JAX_PLATFORMS=cpu python -c 'from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)'; }
run_smoke()  { python chip_smoke.py; }
run_tpu()    { DL4J_TPU_TESTS=1 python -m pytest tests/test_tpu.py -q; }

case "$tier" in
  cpu)    run_cpu ;;
  dryrun) run_dryrun ;;
  smoke)  run_smoke ;;
  tpu)    run_tpu ;;
  all)    run_cpu; run_dryrun; run_smoke; run_tpu ;;
  *) echo "usage: $0 [cpu|dryrun|smoke|tpu|all]" >&2; exit 2 ;;
esac
