"""Test harness: 8 virtual CPU devices so multi-chip sharding logic runs
without TPU hardware (the reference's Spark local[N] pattern — SURVEY.md §4:
'multi-node is simulated ... correctness of distribution is proven by
equivalence to local sequential math').

Two tiers:

- default: everything runs on the virtual CPU mesh; tests marked ``tpu``
  are skipped.
- ``DL4J_TPU_TESTS=1 python -m pytest tests/test_tpu.py``: the real-device
  tier — the platform is left to JAX (the chip), only ``tpu``-marked
  tests run (compiled non-interpret Pallas kernels, donation, bf16, one
  real SyncTrainingMaster step), and compiled programs persist in the
  shared compile cache (``backend/compile_cache.py``).
"""

import os

import pytest

TPU_MODE = os.environ.get("DL4J_TPU_TESTS") == "1"

if not TPU_MODE:
    # Read by the CPU client at first backend init (lazy), so setting it here
    # works even if jax itself is already imported.
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    # float64 available for gradient-check precision (tests opt in per-array)
    jax.config.update("jax_enable_x64", True)
else:
    import jax  # real platform; no x64 (TPUs have no native f64)

    from deeplearning4j_tpu.backend.compile_cache import enable_compile_cache

    enable_compile_cache()

import numpy as np


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: must run on a real TPU chip "
        "(DL4J_TPU_TESTS=1 python -m pytest -m tpu)")
    config.addinivalue_line(
        "markers",
        "faults: fault-injection / chaos tests driving the resilience "
        "subsystem (python -m pytest -m faults)")
    config.addinivalue_line(
        "markers",
        "elastic: degraded-mode data parallelism and topology-portable "
        "resharded-resume tests (python -m pytest -m elastic)")
    config.addinivalue_line(
        "markers",
        "profiling: performance-attribution tests — step profiler "
        "captures, XLA cost analysis / MFU gauges, request tracing, the "
        "kernel-trust rule engine (python -m pytest -m profiling)")
    config.addinivalue_line(
        "markers",
        "online: continuous-learning pipeline tests — stream consumption "
        "with quarantine, windowed incremental fit, SLO-gated promotion, "
        "canary, hot-swap watch + automatic rollback "
        "(python -m pytest -m online)")
    config.addinivalue_line(
        "markers",
        "lint: source-level static-analysis gates — the dl4jlint rule "
        "suite, its ratcheting baseline, and the metrics-docs "
        "shim (python -m pytest -m lint)")
    config.addinivalue_line(
        "markers",
        "stability: training-stability engine tests — device-side "
        "non-finite step guard, loss scaling, divergence sentinel with "
        "auto-rewind, per-replica poison masking "
        "(python -m pytest -m stability)")
    config.addinivalue_line(
        "markers",
        "introspect: training-introspection tests — device-side "
        "per-layer gradient/update/activation stats, anomaly rules, "
        "SSE/run-comparison UI endpoints, crash-safe stats storage "
        "(python -m pytest -m introspect)")
    config.addinivalue_line(
        "markers",
        "zero: ZeRO update-sharding tests — reduce-scatter/all-gather "
        "decomposition of the weight update, sharded updater state, "
        "replicated-vs-ZeRO oracles, projection-vs-actual ledger, "
        "checkpoint interop (python -m pytest -m zero)")
    config.addinivalue_line(
        "markers",
        "generation: continuous-batching generation-engine tests — "
        "paged KV cache with prefix sharing, iteration-level join/leave "
        "scheduling, zero-recompile decode, hot-swap under decode load, "
        "streaming HTTP surface (python -m pytest -m generation)")
    config.addinivalue_line(
        "markers",
        "numerics: precision-observability tests — the in-graph "
        "precision ledger (dynamic-range stats, format-safety verdicts, "
        "spike drill), KV-page range stats, and the kernel-trust "
        "differential harness (python -m pytest -m numerics)")
    config.addinivalue_line(
        "markers",
        "prefix_cache: persistent radix-tree prefix-cache tests — "
        "cross-request KV reuse, pinning, host-tier offload/restore "
        "round-trips, cache-aware admission, invalidation-on-swap, and "
        "the seeded cache-invariant fuzzer "
        "(python -m pytest -m prefix_cache)")
    config.addinivalue_line(
        "markers",
        "fleet: fleet telemetry plane tests — cross-process metrics "
        "federation (schema-versioned snapshots, epoch/seq delta merge, "
        "staleness), decode SLO attribution (TTFT/ITL/goodput, phase "
        "breakdown), and the router-facing cache stats surface "
        "(python -m pytest -m fleet)")
    config.addinivalue_line(
        "markers",
        "kernels: fused-kernel tests — the Pallas paged decode-attention "
        "kernel (lax + interpret impls vs the gather oracle, engine-level "
        "parity) and the fused dropout/residual/norm train epilogue "
        "(parity, grads, dropout-mask bit-identity) "
        "(python -m pytest -m kernels)")
    config.addinivalue_line(
        "markers",
        "fleet_router: serving-fleet control-plane tests — cache-aware "
        "placement (prefix affinity, seeded ties, canary split), "
        "health-gated membership, SIGKILL failover with queued-request "
        "retry and session re-pin, fleet-wide canary rollout with "
        "auto-rollback, replica supervisor lifecycle "
        "(python -m pytest -m fleet_router)")


def pytest_collection_modifyitems(config, items):
    if TPU_MODE:
        skip = pytest.mark.skip(
            reason="CPU-tier test skipped in real-TPU mode (run without "
                   "DL4J_TPU_TESTS for the full suite)")
        for item in items:
            if "tpu" not in item.keywords:
                item.add_marker(skip)
    else:
        skip = pytest.mark.skip(reason="requires a real TPU "
                                       "(DL4J_TPU_TESTS=1 -m tpu)")
        for item in items:
            if "tpu" in item.keywords:
                item.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.RandomState(12345)
