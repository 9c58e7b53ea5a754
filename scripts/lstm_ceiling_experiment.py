"""LSTM throughput-ceiling experiment.

The claim under test: the GravesLSTM bench's low MFU is "intrinsic to the
architecture" (T sequential [B,H]x[H,4H] matmuls cannot fill the MXU);
this script MEASURES that claim instead of asserting it (reference analog:
``LSTMHelpers.java:144-181`` — the cuDNN path has the same shape problem).

Three measurements on the forward scan (``recurrent._scan_lstm``, the
bench config 2x200 H, T=50, fp32), each timed at batch 128 / 512 / 1024:

1. ``scan``       — the real path: input projection as ONE [B*T, in]x[in,4H]
                    matmul + lax.scan of the recurrent cell.
2. ``no_recur``   — the same total FLOPs with the sequential chain removed:
                    xproj plus ONE [B*T, H]x[H,4H] matmul + the gate
                    nonlinearities applied blockwise.  This is the upper
                    bound ANY fused cell kernel (Pallas included) could
                    reach only by eliminating the dependency — which no
                    kernel can; it bounds the win from below-cell fusion.
3. ``matmul_only``— the scan with the cell's elementwise gates stripped
                    (recurrent matmul + add only): isolates how much of a
                    scan step is gate arithmetic (what a fused Pallas cell
                    kernel WOULD save) vs the matmul itself.

Interpretation: if scan/no_recur >> 1 while scan/matmul_only ~ 1, the
ceiling is the recurrence (wider batch is the only lever, until the
[B,H]x[H,4H] step matmul saturates the unit) and a hand-written cell
kernel cannot move it.

Run on any platform; writes profiles/lstm_ceiling.json (git-ignored).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, warmup=2, iters=5):
    import jax

    out = None
    for _ in range(warmup):
        out = fn()
    np.asarray(jax.device_get(out)).ravel()[:1]
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    np.asarray(jax.device_get(out)).ravel()[:1]
    return (time.perf_counter() - t0) / iters


def run(T=50, H=200, n_in=200, batches=(128, 512, 1024)):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.recurrent import (
        _lstm_init, _scan_lstm,
    )

    act = jnp.tanh
    gate = jax.nn.sigmoid
    params = _lstm_init(jax.random.PRNGKey(0), n_in, H, "xavier", None,
                        peephole=True, dtype=jnp.float32)

    rows = {}
    for B in batches:
        x = jnp.asarray(np.random.RandomState(0)
                        .rand(B, T, n_in).astype(np.float32))

        scan_fn = jax.jit(lambda p, x: _scan_lstm(
            p, act, gate, True, x, None)[0])

        def no_recur(p, x):
            B_, T_, _ = x.shape
            xproj = (x.reshape(B_ * T_, -1) @ p["W"] + p["b"])
            z = xproj + xproj[:, :H] @ p["RW"]
            zi, zf, zg, zo = (z[:, i * H:(i + 1) * H] for i in range(4))
            c = gate(zf) * act(zg) + gate(zi) * act(zg)
            return (gate(zo) * act(c)).reshape(B_, T_, H)

        no_recur_fn = jax.jit(no_recur)

        def matmul_only_cell(h_prev, c_prev, xp_t, p):
            z = xp_t + h_prev @ p["RW"]
            return z[:, :H] + c_prev, c_prev + z[:, H:2 * H]

        def matmul_only(p, x):
            B_, T_, _ = x.shape
            xproj = (x.reshape(B_ * T_, -1) @ p["W"] + p["b"]
                     ).reshape(B_, T_, 4 * H)

            def body(carry, xp_t):
                h, c = matmul_only_cell(carry[0], carry[1], xp_t, p)
                return (h, c), h

            z0 = jnp.zeros((B_, H), x.dtype)
            _, ys = jax.lax.scan(body, (z0, z0),
                                 jnp.swapaxes(xproj, 0, 1))
            return jnp.swapaxes(ys, 0, 1)

        matmul_only_fn = jax.jit(matmul_only)

        t_scan = _time(lambda: scan_fn(params, x))
        t_flat = _time(lambda: no_recur_fn(params, x))
        t_mm = _time(lambda: matmul_only_fn(params, x))
        rows[B] = {
            "scan_ms": round(t_scan * 1e3, 3),
            "no_recur_ms": round(t_flat * 1e3, 3),
            "matmul_only_ms": round(t_mm * 1e3, 3),
            "recurrence_cost_x": round(t_scan / t_flat, 2),
            "gate_overhead_x": round(t_scan / t_mm, 2),
            "chars_per_sec": round(B * T / t_scan, 0),
        }
        print(f"B={B}: {rows[B]}")
    return rows


def main():
    import jax

    rows = run()
    out = {
        "config": "T=50 H=200 n_in=200 fp32, forward scan",
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "by_batch": rows,
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "profiles", "lstm_ceiling.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "by_batch"}))


if __name__ == "__main__":
    main()
