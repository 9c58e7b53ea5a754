"""Job ``serve_window_moe``: ``jobs/serve.py``'s closed loop (its clients,
window, end-to-end numbers, release and sample of requests, by import)
around a net that ``model_laguna`` builds — window and full attention layers
over pools of two kinds, routed experts as one chip's share — with the
weights and the reference of its own (the fault, a token altered in the decode
program's output, is ``jobs/serve_latent_moe.py``'s).

``correct``: as in ``jobs/serve_latent_moe.py`` — the plain reference
(``reference_laguna``, layer by layer and in query blocks, after the
program's state is released) runs once over each sampled request's prompt
with its served tokens; ``served_logit_gap_mean`` is the mean gap by which a
served greedy token's logit lies below the reference's best at its position,
``served_logit_gap`` the widest.

The window also brackets the program's expert counters
(``dl4j_moe_tokens_total``, ``dl4j_moe_held_assignments_total``) and, in a
traced run, samples the page gauges (``dl4j_kv_pages_in_use{kind}``) for the
per-layer metrics that read them.

Under ``--rehearsal`` the toy sizes come from
``benchmark/rehearsal_serve_window_moe.json``.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import numpy as np

from benchmark import model_laguna, reference_laguna as ref, traffic_gen
from benchmark.jobs import serve
from benchmark.jobs.serve import end_to_end, release  # noqa: F401  (the job's)
# what does not touch a reference is Kimi's job's, as it stands: the expert
# counters read from the registry, the padded length, the fault
from benchmark.jobs.serve_latent_moe import (
    _pad_to, _plant_token_altered, moe_counts,
)

HERE = os.path.dirname(os.path.abspath(__file__))
GAUGE_PERIOD_S = 0.05


def _apply_toy(ctx):
    with open(os.path.join(HERE, os.pardir,
                           "rehearsal_serve_window_moe.json")) as f:
        toy = json.load(f)
    ctx.config.update(toy["config"])
    ctx.limits.clear()             # the toy's numbers, not the cell's
    ctx.limits.update(toy["limits"])
    for key, val in toy["traffic"].items():
        if isinstance(val, dict) and isinstance(ctx.traffic.get(key), dict):
            ctx.traffic[key].update(val)
        else:
            ctx.traffic[key] = val


def setup(ctx):
    from deeplearning4j_tpu.generation.engine import GenerationEngine

    if ctx.rehearsal:
        _apply_toy(ctx)
    job = ctx.state = serve.ServeJob(ctx)
    cfg, tr, eng = ctx.config, ctx.traffic, ctx.traffic["engine"]
    if tr["loop"] != "closed":
        raise ValueError("jobs/serve_window_moe.py drives closed loops only")
    net = model_laguna.install_weights(model_laguna.build_network(cfg), cfg,
                                       ctx.seed)
    job.sizes = traffic_gen.request_sizes(tr, ctx.seed, 64 * tr["block"])
    longest = max(p + o for p, o in job.sizes)
    if longest > eng["max_context"]:
        raise ValueError(f"a request of {longest} tokens exceeds the "
                         f"context {eng['max_context']}")
    job.engine = GenerationEngine(
        net, slots=eng["slots"], page_size=eng["page_size"],
        max_context=eng["max_context"],
        prefill_buckets=tuple(eng["prefill_buckets"]),
        prefix_cache=eng["prefix_cache"], max_queue=eng["max_queue"],
        deadline_s=eng["deadline_s"])
    job.net, job.experts = net, cfg["num_experts"]
    kinds = [ref.is_sliding(cfg, i) for i in range(cfg["num_hidden_layers"])]
    job.layers_of = {"window": sum(kinds), "global": len(kinds) - sum(kinds)}
    if ctx.fault == "token_altered":
        _plant_token_altered(job.engine, job.vocab)
    job.engine.start()
    job.threads = [threading.Thread(target=job.client, daemon=True,
                                    name=f"client-{i}")
                   for i in range(tr["clients"])]
    for t in job.threads:
        t.start()
    time.sleep(tr["ramp_s"])


def window_page_share(job):
    """Bytes of window-kind pages in use over the bytes of all pages in use,
    now: pages of a kind times the layers that have a pool of it (a page of
    either kind is the same bytes in one layer).  None where the program has
    no such gauges, or nothing is held."""
    reg, eid = job.engine.metrics.registry, job.engine.metrics.engine_id
    held = {kind: (reg.get_value("dl4j_kv_pages_in_use", engine=eid,
                                 kind=kind) or 0.0) * job.layers_of[kind]
            for kind in job.layers_of}
    total = sum(held.values())
    return held["window"] / total if total else None


def window(ctx, seconds):
    job, span = ctx.state, ctx.window_span

    @contextlib.contextmanager
    def counted():
        before = moe_counts(job)
        shares, done = [], threading.Event()

        def sample():
            while not done.wait(GAUGE_PERIOD_S):
                share = window_page_share(job)
                if share is not None:
                    shares.append(share)

        sampler = threading.Thread(target=sample, daemon=True,
                                   name="page-gauges")
        if ctx.traced:             # only a traced run reports the metric
            sampler.start()
        with span():
            yield
        done.set()
        ctx.obs["moe_counts"] = moe_counts(job) - before
        if shares:
            ctx.obs["kv_window_page_share"] = float(np.mean(shares))

    ctx.window_span = counted
    serve.window(ctx, seconds)


def token_gaps(cfg, seed, requests, pad_to, controls=()):
    """``{"f32": {number: value}, control: {...}, ...}`` and the count of
    tokens judged, as ``jobs/serve_latent_moe.token_gaps`` computes them,
    against ``reference_laguna``: one padded shape (causal, no expert has a
    capacity, so the padding is never seen), at ``f32`` and at each precision
    of ``controls``; under ``f32`` the token judged is the served one, under
    a control the one that precision puts first."""
    seqs, at = [], []
    for r in requests:
        seq = np.zeros(pad_to, np.int32)
        n = r.prompt_len + len(r.tokens)
        seq[:r.prompt_len] = traffic_gen.prompt_ids(
            seed, r.index, r.prompt_len, cfg["vocab_size"])
        seq[r.prompt_len:n] = r.tokens
        seqs.append(seq)
        at.append(np.arange(r.prompt_len - 1, n - 1))   # predicts token at+1
    hidden = ref.hidden_states(cfg, seed, seqs, ("f32",) + tuple(controls))
    head_w, head_b = ref.head_leaves(cfg, seed)
    gaps = {p: [] for p in hidden}
    for i, r in enumerate(requests):
        rows = np.asarray(ref.logits_of(hidden["f32"][i][at[i]], head_w,
                                        head_b))
        for p in hidden:
            if p == "f32":
                judged = np.asarray(r.tokens)
            else:
                judged = np.asarray(ref.logits_of(
                    hidden[p][i][at[i]], head_w, head_b, p)).argmax(axis=-1)
            gaps[p].append(rows.max(axis=-1)
                           - rows[np.arange(len(judged)), judged])
    out = {}
    for p, parts in gaps.items():
        allgaps = np.concatenate(parts)
        out[p] = {"served_logit_gap": float(allgaps.max()),
                  "served_logit_gap_mean": float(allgaps.mean())}
    return out, int(sum(len(a) for a in at))


def check(ctx):
    picked = serve.sample_requests(ctx)
    if not picked:
        return [(name, float("nan"), ctx.limits[name])
                for name in ctx.limits]
    numbers, count = token_gaps(ctx.config, ctx.seed, picked, _pad_to(ctx))
    ctx.obs["notes"].update(checked_requests=len(picked),
                            checked_tokens=count)
    return [(name, numbers["f32"][name], limit)
            for name, limit in ctx.limits.items()]


def calibrate(ctx, with_control):
    """The readings a limit is set from (``benchmark/calibrate.py``): the
    program's numbers on this run's sample; and, with ``with_control``, on
    the same prompts and tokens, those of the token that bfloat16 (a second
    witness) and fp8 (the control) put first."""
    picked = serve.sample_requests(ctx)
    controls = ("bf16", "fp8") if with_control else ()
    numbers, count = token_gaps(ctx.config, ctx.seed, picked, _pad_to(ctx),
                                controls)
    out = {"program": {**numbers["f32"], "tokens": count,
                       "requests": len(picked)}}
    for name, prec in (("reference_bf16", "bf16"), ("control_fp8", "fp8")):
        if prec in numbers:
            out[name] = numbers[prec]
    return out
