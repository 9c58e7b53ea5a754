"""Capture XLA profiler traces of the three benchmark models on the TPU.

Produces ``profiles/<model>/`` XPlane traces (TensorBoard 'Profile' tab,
git-ignored) and prints a JSON summary of measured step time vs the compiled
step's XLA cost analysis (FLOPs + bytes accessed) — evidence on the XLA-conv
thesis (≙ deeplearning4j-cuda's claim that the helper kernels beat the
builtin path — here the question is whether stock XLA fusion suffices).

Run: ``python profile_tpu.py`` (real chip; ~2 min).
"""

import json
import os
import time

import numpy as np


def _trace(name, step, args_fn, steps=8):
    import jax

    out_dir = os.path.join("profiles", name)
    os.makedirs(out_dir, exist_ok=True)
    state, make_args = args_fn
    # warmup/compile outside the trace
    for _ in range(3):
        state = step(state, make_args())
    np.asarray(jax.device_get(state[-1]))
    jax.profiler.start_trace(out_dir)
    t0 = time.perf_counter()
    for _ in range(steps):
        state = step(state, make_args())
    np.asarray(jax.device_get(state[-1]))
    dt = (time.perf_counter() - t0) / steps
    jax.profiler.stop_trace()
    return dt


def main():
    import jax
    import jax.numpy as jnp

    from bench import _compile_step, _peak_flops
    from deeplearning4j_tpu.models.zoo import (
        graves_lstm_char_lm, lenet, resnet50,
    )

    dev = jax.devices()[0]
    peak = _peak_flops(dev)
    rs = np.random.RandomState(0)
    report = {"device": getattr(dev, "device_kind", "?"), "models": {}}

    # ---- LeNet fp32 b128
    net = lenet(updater="nesterovs", lr=0.01)
    x = jnp.asarray(rs.rand(128, 784).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rs.randint(0, 10, 128)])
    jstep = net._get_train_step()
    flops, compiled = _compile_step(jstep, net.params, net.updater_state,
                                    net.net_state, jnp.zeros(()), x, y,
                                    net._keys.next(), None, None, None)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost

    def step_lenet(state, _):
        p, u, n, loss, _c = compiled(state[0], state[1], state[2],
                                     jnp.zeros(()), x, y, net._keys.next(),
                                     None, None, None)
        return [p, u, n, loss]

    dt = _trace("lenet", step_lenet,
                ([net.params, net.updater_state, net.net_state, None],
                 lambda: None))
    report["models"]["lenet_b128_fp32"] = {
        "step_ms": round(dt * 1e3, 3), "flops": flops,
        "bytes_accessed": cost.get("bytes accessed", None),
        "mfu_vs_bf16_peak": round(flops / dt / peak, 4) if peak else None,
    }

    # ---- ResNet-50 bf16 b128
    net2 = resnet50(compute_dtype="bfloat16")
    x2 = {"input": jnp.asarray(rs.rand(128, 224, 224, 3).astype(np.float32))}
    y2 = {"fc": jnp.asarray(np.eye(1000, dtype=np.float32)[rs.randint(0, 1000, 128)])}
    jstep2 = net2._get_train_step()
    flops2, compiled2 = _compile_step(jstep2, net2.params, net2.updater_state,
                                      net2.net_state, jnp.zeros(()), x2, y2,
                                      net2._keys.next(), None, None, None)
    cost2 = compiled2.cost_analysis()
    cost2 = cost2[0] if isinstance(cost2, (list, tuple)) else cost2

    def step_resnet(state, _):
        p, u, n, loss, _c = compiled2(state[0], state[1], state[2],
                                      jnp.zeros(()), x2, y2,
                                      net2._keys.next(), None, None, None)
        return [p, u, n, loss]

    dt2 = _trace("resnet50", step_resnet,
                 ([net2.params, net2.updater_state, net2.net_state, None],
                  lambda: None))
    report["models"]["resnet50_b128_bf16"] = {
        "step_ms": round(dt2 * 1e3, 2), "flops": flops2,
        "bytes_accessed": cost2.get("bytes accessed", None),
        "mfu": round(flops2 / dt2 / peak, 4) if peak else None,
    }

    # ---- GravesLSTM fp32 b128 T50
    net3 = graves_lstm_char_lm(vocab_size=77, hidden=200, tbptt=50)
    ids = rs.randint(0, 77, (128, 50))
    x3 = jnp.asarray(np.eye(77, dtype=np.float32)[ids])
    y3 = jnp.asarray(np.eye(77, dtype=np.float32)[np.roll(ids, -1, 1)])
    jstep3 = net3._get_train_step()
    flops3, compiled3 = _compile_step(jstep3, net3.params, net3.updater_state,
                                      net3.net_state, jnp.zeros(()), x3, y3,
                                      net3._keys.next(), None, None, None)
    cost3 = compiled3.cost_analysis()
    cost3 = cost3[0] if isinstance(cost3, (list, tuple)) else cost3

    def step_lstm(state, _):
        p, u, n, loss, _c = compiled3(state[0], state[1], state[2],
                                      jnp.zeros(()), x3, y3,
                                      net3._keys.next(), None, None, None)
        return [p, u, n, loss]

    dt3 = _trace("graves_lstm", step_lstm,
                 ([net3.params, net3.updater_state, net3.net_state, None],
                  lambda: None))
    report["models"]["graves_lstm_b128_t50_fp32"] = {
        "step_ms": round(dt3 * 1e3, 2), "flops": flops3,
        "bytes_accessed": cost3.get("bytes accessed", None),
        "mfu_vs_bf16_peak": round(flops3 / dt3 / peak, 4) if peak else None,
    }

    print(json.dumps(report))
    with open("profiles/summary.json", "w") as f:
        json.dump(report, f, indent=2)


if __name__ == "__main__":
    main()
