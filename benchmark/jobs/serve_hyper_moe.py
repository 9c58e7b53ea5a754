"""Job ``serve_hyper_moe``: ``jobs/serve.py``'s closed loop (its clients,
window, end-to-end numbers, release and sample of requests, by import)
around a net that ``model_xing`` builds — four residual streams mixed by
manifold-constrained hyper-connections around latent attention and an
expert layer that holds every expert — with the weights, the reference and
the fault of its own.

``correct``: as in ``jobs/serve_latent_moe.py``, against ``reference_xing``
(layer by layer, after the program's state is released): over every served
position of the sampled requests, the gap by which the served greedy
token's reference logit lies below the reference's best; the widest
(``served_logit_gap``) and the mean (``served_logit_gap_mean``).

The window brackets the program's expert counters (``serve_latent_moe``'s
reading of them) and, in a traced run, samples the gauge
``dl4j_mhc_row_sum_error``; a traced run also keeps, at set-up, the map from
device operation to ``jax.named_scope`` of each compute program
(``metrics/_scopes.py``), from the programs' own compiled text.

``rehearsal.json`` has no place for a new job's toy sizes: under
``--rehearsal`` they come from ``benchmark/rehearsal_serve_hyper_moe.json``.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

import numpy as np

from benchmark import model_xing, reference_xing as ref, traffic_gen
from benchmark.jobs import serve
from benchmark.jobs.serve import end_to_end, release  # noqa: F401  (the job's)
from benchmark.jobs.serve_latent_moe import _pad_to, moe_counts
from benchmark.metrics import _scopes

HERE = os.path.dirname(os.path.abspath(__file__))
GAUGE_PERIOD_S = 0.05


def _apply_toy(ctx):
    with open(os.path.join(HERE, os.pardir,
                           "rehearsal_serve_hyper_moe.json")) as f:
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
        raise ValueError("jobs/serve_hyper_moe.py drives closed loops only")
    net = model_xing.install_weights(model_xing.build_network(cfg), cfg,
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
    job.net, job.experts = net, cfg["n_routed_experts"]
    if ctx.fault == "token_altered":
        _plant_token_altered(job.engine, job.vocab)
    job.engine.start()
    if ctx.traced:
        progs = next(iter(job.engine._programs.values()))
        ctx.obs["scope_maps"] = _scopes.of_programs(
            {name: low.compile().as_text()
             for name, low in progs.lowered().items()})
    job.threads = [threading.Thread(target=job.client, daemon=True,
                                    name=f"client-{i}")
                   for i in range(tr["clients"])]
    for t in job.threads:
        t.start()
    time.sleep(tr["ramp_s"])


def row_sum_error(job):
    """The gauge now; None where the program has none."""
    m = job.engine.metrics
    return m.registry.get_value("dl4j_mhc_row_sum_error", engine=m.engine_id)


def window(ctx, seconds):
    job, span = ctx.state, ctx.window_span

    @contextlib.contextmanager
    def counted():
        before = moe_counts(job)
        errors, done = [], threading.Event()

        def sample():
            while not done.wait(GAUGE_PERIOD_S):
                err = row_sum_error(job)
                if err is not None:
                    errors.append(err)

        sampler = threading.Thread(target=sample, daemon=True,
                                   name="mhc-gauge")
        if ctx.traced:             # only a traced run reports the metric
            sampler.start()
        with span():
            yield
        done.set()
        ctx.obs["moe_counts"] = moe_counts(job) - before
        if errors:
            ctx.obs["mhc_row_sum_error"] = float(max(errors))

    ctx.window_span = counted
    serve.window(ctx, seconds)


def token_gaps(cfg, seed, requests, pad_to, controls=()):
    """``{"f32": {number: value}, control: {...}, ...}`` and the count of
    tokens judged, as ``jobs/serve_latent_moe.py`` computes them, against
    ``reference_xing``: the reference runs once over every request's prompt
    + served tokens (one padded shape; causal, and no expert has a capacity,
    so the padding is never seen), at ``f32`` and at each precision of
    ``controls``; at every served position the gap is the reference's best
    logit less its logit of the token judged (under ``f32`` the served
    token, under a control the token that precision puts first).  The head
    runs over the served positions only, a block of them at a time."""
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
        rows = np.asarray(ref.logits_in_blocks(hidden["f32"][i][at[i]],
                                               head_w, head_b))
        for p in hidden:
            if p == "f32":
                judged = np.asarray(r.tokens)
            else:
                judged = np.asarray(ref.logits_in_blocks(
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


# --------------------------------------------------- faults (tests only)
def _plant_token_altered(engine, vocab):
    """A token altered where it is produced: the decode program's sampled
    ids come back shifted by one (its counts and its gauge untouched)."""
    build = engine._build_programs

    def patched(mv):
        progs = build(mv)
        real = progs._decode

        def altered(*a):
            pools, (tok, *rest) = real(*a)
            return pools, ((tok + 1) % vocab, *rest)
        progs._decode = altered
        return progs
    engine._build_programs = patched
