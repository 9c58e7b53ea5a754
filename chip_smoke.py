"""The quickest proof that the system still starts on the chip.

One process drives the two normal entry points once, at the full width of
the flagship transformer (d1024, 8 heads of 128, 8 layers, bf16, seeded
random weights):

- trainer: ``MultiLayerNetwork.fit`` for a few steps at batch 8 x T 2048 on
  a repeated seeded batch — loss finite every step, lower at the end, no
  compile after the first step;
- server: ``GenerationEngine`` (16 slots, pages of 16, context 512, prefix
  cache) behind ``InferenceServer``; overlapping requests of mixed lengths,
  some over ``POST /generate`` (SSE) on loopback — every request returns
  exactly the tokens it asked for, none errors, no compile after start, and
  each greedy request's first token agrees with a full-sequence forward;
- with more than one device, the same model under
  ``DistributedNetwork(SyncTrainingMaster)`` over all of them — shardings
  span every device, every device holds bytes, step-1 loss equals the
  one-device run.

It also proves which kernels the programs were built from, by counting the
Mosaic custom calls in each program's HLO: nothing else records whether a
helper's ``supports()`` chose the Pallas path or gave way to stock jnp.

    python chip_smoke.py               needs a TPU; prints the report, one
                                       JSON line {"rehearsal": false, ...},
                                       then last, and with exactly these keys,
                                       {"ok": true, "device": {"platform":
                                       ..., "kind": ..., "count": ...}}
    python chip_smoke.py --rehearsal   tiny size on whatever backend JAX has
                                       (control flow only); prints the report
                                       {"rehearsal": true, ...}, never "ok"

Any failed check exits non-zero with the reason last on stderr and no
result line on stdout.
"""

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

SEED = 20260926
VOCAB = 128

FULL = dict(d_model=1024, n_heads=8, layers=8, batch=8, seq=2048, steps=6,
            slots=16, page_size=16, max_context=512, buckets=(16, 128),
            # (prompt length, new tokens, temperature, transport)
            requests=((100, 24, 0.0, "direct"), (5, 48, 0.0, "direct"),
                      (16, 32, 0.0, "sse"), (128, 16, 0.0, "direct"),
                      (40, 64, 0.8, "direct"), (100, 8, 0.0, "sse"),
                      (12, 40, 0.8, "http"), (77, 20, 0.0, "direct"),
                      (30, 56, 0.0, "direct"), (64, 12, 0.0, "sse")))
TINY = dict(d_model=64, n_heads=2, layers=2, batch=2, seq=256, steps=5,
            slots=4, page_size=16, max_context=128, buckets=(16, 64),
            requests=((40, 8, 0.0, "direct"), (5, 12, 0.0, "direct"),
                      (16, 6, 0.0, "sse"), (64, 4, 0.0, "direct"),
                      (20, 10, 0.8, "direct"), (40, 3, 0.0, "sse"),
                      (12, 7, 0.8, "http"), (33, 5, 0.0, "direct")))

# A greedy request's first token must be (near) the argmax of a
# full-sequence forward of its prompt: log p[token] >= max log p - this.
# The two paths round differently in bf16 (8 mantissa bits: ~4e-3 per
# rounding, compounding over 8 layers to a few 1e-2 in the logits), so
# near-ties may flip; a wrong cache position or mask picks at random.
FIRST_TOKEN_LOGP_TOL = 0.25
# Step-1 loss, data-parallel over N devices vs one device, same seed and
# batch: the forward is the same math per example, but partitioning the
# batch changes matmul tiling and the order of the f32 reductions over
# bf16 products.
DP_LOSS_RTOL = 1e-2


def fail(reason: str):
    raise SystemExit(f"chip_smoke: FAILED: {reason}")


class CompileLog:
    """Counts real XLA compilations (cache hits included) from JAX's own
    monitoring events — stricter than a signature fingerprint."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            if name.endswith("backend_compile_duration"):
                self.compiles += 1

    def _event(self, name, **_):
        if name.endswith("/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("/cache_misses"):
            self.cache_misses += 1


def cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def kernel_counts(lowered):
    """Pallas kernels in one lowered program, by name, from its StableHLO."""
    import re

    names = re.findall(r'kernel_name = "([^"]+)"', lowered.as_text())
    return {n: names.count(n) for n in sorted(set(names))}


def mosaic_report(lowered):
    """``kernel_counts`` plus the custom calls that survive into the
    compiled HLO (one more compile; the persistent cache has it)."""
    return {"mosaic_calls": lowered.compile().as_text().count(
                'custom_call_target="tpu_custom_call"'),
            "kernels": kernel_counts(lowered)}


def build_net(cfg):
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    return transformer_char_lm(
        vocab_size=VOCAB, d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        layers=cfg["layers"], compute_dtype="bfloat16",
        max_cache=cfg["max_context"], updater="adam", lr=1e-4, seed=SEED)


def token_batch(cfg):
    import numpy as np

    ids = np.random.RandomState(SEED).randint(
        0, VOCAB, (cfg["batch"], cfg["seq"])).astype(np.int32)
    labels = np.eye(VOCAB, dtype=np.float32)[np.roll(ids, -1, 1)]
    return ids, labels


def run_steps(what, n, log, one_step):
    """``n`` training steps on the repeated batch, each ending in a
    ``device_get`` of its loss.  Checks: finite, lower at the end, no
    compile after the first step.  Returns the per-phase report fields."""
    import math

    c0, s0 = log.compiles, log.seconds
    losses, step_s, first = [], [], None
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(float(one_step()))
        step_s.append(round(time.perf_counter() - t0, 4))
        if first is None:
            first = log.compiles - c0
    if not all(math.isfinite(l) for l in losses):
        fail(f"{what}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        fail(f"{what}: loss did not fall on a repeated batch: {losses}")
    steady = log.compiles - c0 - first
    if steady:
        fail(f"{what}: {steady} compile(s) after the first step")
    return {"steps": n, "losses": [round(l, 3) for l in losses],
            "compile_s": round(log.seconds - s0, 2), "compiles": first,
            "steady_compiles": steady, "first_step_s": step_s[0],
            "steady_step_s": step_s[1:], "run_s": round(sum(step_s[1:]), 3)}


# ------------------------------------------------------------------ trainer
def phase_train(cfg, log, on_tpu):
    import jax
    import jax.numpy as jnp

    net = build_net(cfg)
    x, y = token_batch(cfg)

    def one_step():
        net.fit(x, y)
        return jax.device_get(net.score_value)

    out = run_steps("train", cfg["steps"], log, one_step)
    detector = net._get_train_step().detector
    if detector.compile_count != 1:
        fail(f"train: dl4j_compiles_total counts "
             f"{detector.compile_count} train-step signatures, want 1")
    lowered = net._get_train_step().lower(
        net.params, net.updater_state, net.net_state,
        jnp.zeros((), jnp.float32), jnp.asarray(x), jnp.asarray(y),
        jax.random.key(0), None, None, None)
    out.update(mosaic_report(lowered))
    if on_tpu:
        for k in ("flash_attention_fwd", "flash_attention_dq",
                  "flash_attention_dkv"):
            if not out["kernels"].get(k):
                fail(f"train: kernel {k} is not in the train step "
                     f"({out['kernels']})")
        if not out["mosaic_calls"]:
            fail("train: no Mosaic custom call in the compiled train step")
    return out


# ------------------------------------------------------------------- server
def sse_generate(port, body):
    """POST /generate with stream=true; returns (tokens, terminal event)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(dict(body, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    tokens, last = [], None
    with urllib.request.urlopen(req, timeout=600) as resp:
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            last = json.loads(line[5:])
            if "token" in last:
                tokens.append(last["token"])
            if last.get("done"):
                break
    return tokens, last


def http_generate(port, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        obj = json.loads(resp.read().decode())
    return obj["tokens"], obj


def phase_serve(cfg, log, on_tpu):
    import numpy as np

    from deeplearning4j_tpu.generation import GenerationEngine
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.observability import get_flight_recorder
    from deeplearning4j_tpu.streaming.serving import InferenceServer

    net = build_net(cfg)
    rs = np.random.RandomState(SEED + 1)
    specs = cfg["requests"]
    prompts = [rs.randint(1, VOCAB, n).tolist() for n, _, _, _ in specs]
    # requests 0 and 5 ask the same long prompt again: the first, served
    # alone below, leaves its pages in the prefix cache for the others
    prompts[5] = list(prompts[0])

    c0, s0 = log.compiles, log.seconds
    t0 = time.perf_counter()
    engine = GenerationEngine(
        net, slots=cfg["slots"], page_size=cfg["page_size"],
        max_context=cfg["max_context"], prefill_buckets=cfg["buckets"],
        prefix_cache=True, deadline_s=600.0).start()
    srv = None
    try:
        # InferenceServer wants a /predict model too (as fleet/replica_main)
        pconf = (NeuralNetConfiguration.builder().seed(1)
                 .updater("sgd", learning_rate=0.1).list()
                 .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
                 .layer(OutputLayer(n_in=8, n_out=3, loss="mcxent",
                                    activation="softmax")).build())
        srv = InferenceServer(MultiLayerNetwork(pconf).init(),
                              generation=engine)
        port = srv.start()
        start_s = time.perf_counter() - t0
        start_compiles, start_compile_s = log.compiles - c0, log.seconds - s0
        errors0 = sum(e.kind == "generation_error"
                      for e in get_flight_recorder().events())

        results = [None] * len(specs)

        def run(i):
            n_new, temp, via = specs[i][1], specs[i][2], specs[i][3]
            body = {"prompt": prompts[i], "max_tokens": n_new,
                    "temperature": temp, "seed": SEED + i}
            if temp > 0:
                body["top_k"] = 20
            if via == "direct":
                h = engine.submit(prompts[i], max_new_tokens=n_new,
                                  temperature=temp, seed=SEED + i,
                                  top_k=body.get("top_k"))
                results[i] = (list(h.result(timeout=600)), h.finish_reason)
            elif via == "sse":
                toks, last = sse_generate(port, body)
                results[i] = (toks, "error" if "error" in last
                              else last.get("finish_reason"))
            else:
                toks, obj = http_generate(port, body)
                results[i] = (toks, obj.get("finish_reason"))

        mark = log.compiles
        t1 = time.perf_counter()
        run(0)
        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(1, len(specs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        run_s = time.perf_counter() - t1
        steady = log.compiles - mark

        for i, (spec, res) in enumerate(zip(specs, results)):
            if res is None:
                fail(f"serve: request {i} {spec} did not return")
            toks, reason = res
            if reason != "length" or len(toks) != spec[1]:
                fail(f"serve: request {i} {spec} returned {len(toks)} "
                     f"tokens, finish_reason={reason!r}")
            if not all(isinstance(t, int) and 0 <= t < VOCAB for t in toks):
                fail(f"serve: request {i} returned tokens outside the "
                     f"vocabulary: {toks}")
        errors = sum(e.kind == "generation_error"
                     for e in get_flight_recorder().events()) - errors0
        if errors:
            fail(f"serve: {errors} generation_error flight event(s)")
        if steady:
            fail(f"serve: {steady} compile(s) after start()")
        prefix_hits = engine.prefix_cache.stats()["hits"]
        if not prefix_hits:
            fail("serve: the repeated prompt did not hit the prefix cache")

        # reference: one full-sequence forward over the right-padded
        # prompts (causal, so padding cannot reach a prompt's last logit)
        width = max(cfg["buckets"])
        padded = np.zeros((len(prompts), width), np.int32)
        for i, p in enumerate(prompts):
            padded[i, :len(p)] = p
        logp = np.log(np.maximum(np.asarray(net.output(padded)), 1e-30))
        worst = 0.0
        for i, (spec, (toks, _)) in enumerate(zip(specs, results)):
            if spec[2] > 0:
                continue
            row = logp[i, len(prompts[i]) - 1]
            gap = float(row.max() - row[toks[0]])
            worst = max(worst, gap)
            if not gap <= FIRST_TOKEN_LOGP_TOL:
                fail(f"serve: request {i} first token {toks[0]} has "
                     f"log-prob {gap:.3f} below the reference argmax "
                     f"(tolerance {FIRST_TOKEN_LOGP_TOL})")

        out = {"requests": len(specs),
               "over_http": sum(s[3] != "direct" for s in specs),
               "tokens": sum(s[1] for s in specs),
               "compile_s": round(start_compile_s, 2),
               "compiles": start_compiles, "steady_compiles": steady,
               "start_s": round(start_s, 2), "run_s": round(run_s, 3),
               "prefix_cache_hits": prefix_hits,
               "first_token_logp_gap_max": round(worst, 4),
               "programs": {}}
        progs = engine._programs[engine.models.active(
            engine.default_model).key]
        for name, lowered in progs.lowered().items():
            rep = mosaic_report(lowered)
            out["programs"][name] = rep
            if on_tpu:
                for k in ("fused_paged_attention",
                          "fused_dropout_residual_norm"):
                    if not rep["kernels"].get(k):
                        fail(f"serve: kernel {k} is not in {name} "
                             f"({rep['kernels']})")
                if not rep["mosaic_calls"]:
                    fail(f"serve: no Mosaic custom call in compiled {name}")
        return out
    finally:
        if srv is not None:
            srv.stop()
        engine.stop(drain=False)


# ------------------------------------------------------------ data parallel
def phase_dp(cfg, log, loss_one_device, on_tpu):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import backend
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.parallel import (
        DistributedNetwork, SyncTrainingMaster,
    )

    n = jax.device_count()
    if cfg["batch"] % n:
        fail(f"dp: global batch {cfg['batch']} does not divide over {n} "
             "devices")
    net = build_net(cfg)
    x, y = token_batch(cfg)
    master = SyncTrainingMaster(mesh=backend.default_mesh())
    dist = DistributedNetwork(net, master)

    def one_step():
        dist.fit(ListDataSetIterator(DataSet(x, y), cfg["batch"]))
        return jax.device_get(net.score_value)

    out = run_steps("dp", cfg["steps"], log, one_step)
    if len(master._data_sharding.device_set) != n:
        fail(f"dp: batch sharding spans "
             f"{len(master._data_sharding.device_set)} of {n} devices")
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree_util.tree_leaves(net.params)}
    if spans != {n}:
        fail(f"dp: parameter shardings span {sorted(spans)} devices, "
             f"want {n}")
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in jax.devices()]
    if on_tpu and not all(in_use):
        fail(f"dp: a device holds no bytes: bytes_in_use={in_use}")
    # the step XLA partitions by itself must still hold the flash kernels
    # (inside shard_map — see helpers.auto_partitioned)
    kernels = kernel_counts(master._step.lower(
        net.params, net.updater_state, net.net_state, jnp.zeros(()),
        jnp.asarray(x), jnp.asarray(y), jax.random.key(0), None, None))
    if on_tpu and not kernels.get("flash_attention_fwd"):
        fail(f"dp: no flash attention in the partitioned step ({kernels})")
    rel = abs(out["losses"][0] - loss_one_device) / abs(loss_one_device)
    if not rel <= DP_LOSS_RTOL:
        fail(f"dp: step-1 loss {out['losses'][0]} differs from the "
             f"one-device run's {loss_one_device} by {rel:.2e} (tolerance "
             f"{DP_LOSS_RTOL})")
    return dict(out, devices=n, bytes_in_use=in_use, kernels=kernels,
                step1_rel_diff_vs_one_device=float(f"{rel:.3e}"))


# --------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny size on any backend; never prints ok")
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.backend.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)

    import jax
    import jaxlib

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearsal:
        fail(f"JAX found no TPU (platform {dev.platform!r}, device_kind "
             f"{dev.device_kind!r}); the smoke runs on the chip only")
    cfg = TINY if args.rehearsal else FULL
    log = CompileLog()

    from deeplearning4j_tpu import native

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None

    t_all = time.perf_counter()
    train = phase_train(cfg, log, on_tpu)
    serve = phase_serve(cfg, log, on_tpu)
    dp = (phase_dp(cfg, log, train["losses"][0], on_tpu)
          if jax.device_count() > 1 else None)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    report = {
        "rehearsal": args.rehearsal, "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version},
        "model": {k: cfg[k] for k in ("d_model", "n_heads", "layers",
                                      "batch", "seq")},
        "native": "built" if native.available() else "python",
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": cache_entries(cache_dir),
            "hits": log.cache_hits, "misses": log.cache_misses},
        "train": train, "serve": serve, "dp": dp,
        "total_s": round(time.perf_counter() - t_all, 1),
    }
    print(json.dumps(report), flush=True)
    if not args.rehearsal:
        # the result line: last, and nothing in it but these two keys
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
