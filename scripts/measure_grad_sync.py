"""Grad-sync bandwidth CLI — a thin front-end over ``shardstats``.

The reference's analog is the Spark parameter aggregate
(``ParameterAveragingTrainingMaster.java:628-645`` — processParams /
aggregate over the executor fleet).  Here the dp gradient sync is an XLA
all-reduce over the mesh's data axis, and since the sharding-ledger PR
the ONE owner of "bytes moved per sync step" is
``observability.shardstats``: this script builds the ResNet-50-sized
collective, lets the HLO census count its bytes (instead of trusting the
hand-computed number), times it on the virtual mesh, and prices it with
the shared ``LINK_BANDWIDTH`` table + ``ring_wire_bytes`` recipe.

Rows produced:

1. **Measured (virtual mesh)**: wall-clock of ONE psum of a
   ResNet-50-sized gradient tree over an 8-device host-platform CPU mesh
   (validates the collective's program shape; CPU-memory-bound, NOT ICI).
2. **Censused**: the compiled program's all-reduce count/bytes from
   ``shardstats.program_analysis`` — the same census the training
   masters report through ``dl4j_step_collective_bytes``.
3. **Analytic (v5e ICI)**: the same collective priced on a v5e ring from
   ``LINK_BANDWIDTH`` (t = ring_wire_bytes / ICI_bw).

With ``--sharded`` a second arm censuses the ZeRO decomposition
(arXiv 2004.13336, ``parallel/zero.py``): the same gradient tree synced
as reduce-scatter(grads) + all-gather(params) instead of one all-reduce
— the HLO census counts both collectives and their payload bytes next
to the all-reduce arm, and the analytic row prices the ring wire bytes
of the pair (equal: 2(K-1)/K split as (K-1)/K + (K-1)/K).

Run: JAX_PLATFORMS=cpu \
     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python scripts/measure_grad_sync.py [--sharded]
Writes profiles/grad_sync.json (git-ignored) and prints one JSON line.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RESNET50_PARAMS = 25_557_032          # fc + conv + bn weights, our zoo config
DTYPE_BYTES = 4                       # grads sync in f32


def measure(n_devices: int = 8, iters: int = 20):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.observability import shardstats

    devices = jax.devices()[:n_devices]
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))

    # ResNet-50-sized flat gradient, one replica per device (the dp state
    # right before the sync): [N, P] sharded over 'data'
    p = RESNET50_PARAMS
    rows = jnp.asarray(np.random.RandomState(0)
                       .rand(n, p).astype(np.float32))
    rows = jax.device_put(rows, NamedSharding(mesh, P("data")))

    @jax.jit
    def allreduce(rows):
        return jax.shard_map(lambda r: lax.psum(r, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P("data"))(rows)

    # census BEFORE the timed dispatches: the one owner of "bytes moved
    # per sync step" is the HLO count, not the hand math
    analysis = shardstats.program_analysis(allreduce, (rows,), {})
    census = analysis.get("collectives", {})
    ar = census.get("all-reduce", {"count": 0, "bytes": 0,
                                   "group_sizes": []})
    group = (ar["group_sizes"] or [n])[0]

    out = allreduce(rows)
    np.asarray(jax.device_get(out[0, :1]))  # warm + sync
    t0 = time.perf_counter()
    for _ in range(iters):
        out = allreduce(out)
    np.asarray(jax.device_get(out[0, :1]))
    dt = (time.perf_counter() - t0) / iters

    bytes_grad = p * DTYPE_BYTES
    # the census sees the partitioned program: each device's shard_map
    # block is one full [1, P] gradient row, so the psum payload equals
    # the FULL tree bytes (the same number the analytic row prices)
    ring_bytes_per_dev = shardstats.ring_wire_bytes(
        "all-reduce", bytes_grad, group)
    v5e_bw = shardstats.LINK_BANDWIDTH["TPU v5 lite"]
    analytic_s = ring_bytes_per_dev / v5e_bw
    return {
        "metric": "dp grad all-reduce (ResNet-50-sized tree)",
        "params": p,
        "grad_mb": round(bytes_grad / 1e6, 1),
        "n_devices": n,
        "platform": devices[0].platform,
        "measured_ms": round(dt * 1e3, 3),
        "measured_algbw_gbps": round(ring_bytes_per_dev / dt / 1e9, 2),
        "ring_bytes_per_device_mb": round(ring_bytes_per_dev / 1e6, 1),
        "censused_allreduce_count": ar["count"],
        "censused_allreduce_bytes": ar["bytes"],
        "censused_group_size": group,
        "program_memory": analysis.get("memory"),
        "analytic_v5e_ms": round(analytic_s * 1e3, 3),
        "analytic_ici_gbps": v5e_bw / 1e9,
        "note": ("measured on the virtual host-platform mesh (CPU memory "
                 "bandwidth, shared address space — validates the collective "
                 "shape, NOT ICI); collective bytes are the HLO census "
                 "(shardstats.program_analysis) of the partitioned "
                 "program; analytic row prices ring_wire_bytes at the "
                 "LINK_BANDWIDTH['TPU v5 lite'] spec figure"),
    }


def measure_sharded(n_devices: int = 8, iters: int = 20):
    """The ZeRO window's collective pattern over the same
    ResNet-50-sized tree: reduce-scatter the summed gradient, update the
    local 1/K shard (elementwise SGD stand-in), all-gather the params —
    censused with the same PR-14 API as the all-reduce arm."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.observability import shardstats

    devices = jax.devices()[:n_devices]
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))

    p = RESNET50_PARAMS - (RESNET50_PARAMS % n)   # shardable length
    rng = np.random.RandomState(0)
    grads = jax.device_put(
        jnp.asarray(rng.rand(n, p).astype(np.float32)),
        NamedSharding(mesh, P("data")))            # per-replica grads
    params = jax.device_put(
        jnp.asarray(rng.rand(p).astype(np.float32)),
        NamedSharding(mesh, P("data")))            # ZeRO-sharded params

    @jax.jit
    def zero_sync(params, grads):
        def local(p_blk, g_blk):
            # reduce-scatter: the sum of every replica's gradient,
            # delivered as this device's 1/K shard
            g_sh = lax.psum_scatter(g_blk[0], "data",
                                    scatter_dimension=0, tiled=True) / n
            new_p = p_blk - 0.1 * g_sh             # sharded update
            full = lax.all_gather(new_p, "data", axis=0, tiled=True)
            return new_p, full

        return jax.shard_map(local, mesh=mesh,
                         in_specs=(P("data"), P("data")),
                         out_specs=(P("data"), P()),
                         check_vma=False)(params, grads)

    analysis = shardstats.program_analysis(zero_sync, (params, grads), {})
    census = analysis.get("collectives", {})
    rs = census.get("reduce-scatter", {"count": 0, "bytes": 0,
                                       "group_sizes": []})
    ag = census.get("all-gather", {"count": 0, "bytes": 0,
                                   "group_sizes": []})
    group = (rs["group_sizes"] or [n])[0]

    new_p, _full = zero_sync(params, grads)
    np.asarray(jax.device_get(new_p[:1]))          # warm + sync
    t0 = time.perf_counter()
    out_p = params
    for _ in range(iters):
        out_p, _full = zero_sync(out_p, grads)
    np.asarray(jax.device_get(out_p[:1]))
    dt = (time.perf_counter() - t0) / iters

    bytes_grad = p * DTYPE_BYTES
    ring_bytes = (shardstats.ring_wire_bytes("reduce-scatter", bytes_grad,
                                             group)
                  + shardstats.ring_wire_bytes("all-gather", bytes_grad,
                                               group))
    v5e_bw = shardstats.LINK_BANDWIDTH["TPU v5 lite"]
    return {
        "metric": "ZeRO grad reduce-scatter + param all-gather "
                  "(ResNet-50-sized tree)",
        "params": p,
        "grad_mb": round(bytes_grad / 1e6, 1),
        "n_devices": n,
        "platform": devices[0].platform,
        "measured_ms": round(dt * 1e3, 3),
        "ring_bytes_per_device_mb": round(ring_bytes / 1e6, 1),
        "censused_reduce_scatter_count": rs["count"],
        "censused_reduce_scatter_bytes": rs["bytes"],
        "censused_all_gather_count": ag["count"],
        "censused_all_gather_bytes": ag["bytes"],
        "censused_group_size": group,
        "program_memory": analysis.get("memory"),
        "analytic_v5e_ms": round(ring_bytes / v5e_bw * 1e3, 3),
        "analytic_ici_gbps": v5e_bw / 1e9,
        "note": ("the ZeRO window's collective pattern "
                 "(parallel/zero.py): reduce-scatter + all-gather ring "
                 "wire bytes equal the all-reduce arm's 2(K-1)/K — the "
                 "win is the 1/K persistent updater state, not the "
                 "wire; collective bytes are the HLO census"),
    }


def main():
    result = measure()
    if "--sharded" in sys.argv[1:]:
        result = {"allreduce": result, "sharded": measure_sharded()}
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "profiles", "grad_sync.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
