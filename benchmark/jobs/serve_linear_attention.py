"""Job ``serve_linear_attention``: ``jobs/serve.py``'s closed loop, and
``jobs/serve_state_space.py``'s window (the gauge ``dl4j_state_slots_in_use``
sampled in a traced run), release and padding of the compared sequences, by
import, around a net that ``model_olmo_hybrid`` builds — Gated DeltaNet
layers on state slots beside full attention layers on pages — with the
weights, the reference and the two faults of its own.

``correct``: as in ``jobs/serve_state_space.py``, against
``reference_olmo_hybrid`` (layer by layer, after the program's state is
released): over every served position of the sampled requests, the gap by
which the served greedy token's reference logit lies below the reference's
best; the widest (``served_logit_gap``) and the mean
(``served_logit_gap_mean``).  The reference runs the delta rule a position
at a time from zero state, so a slot that kept its last tenant's state, or
a state that bucket padding advanced, shows as a gap.

``rehearsal.json`` has no place for a new job's toy sizes: under
``--rehearsal`` they come from
``benchmark/rehearsal_serve_linear_attention.json``.
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from benchmark import model_olmo_hybrid, reference_olmo_hybrid as ref
from benchmark import traffic_gen
from benchmark.jobs import serve
from benchmark.jobs.serve import (  # noqa: F401  (the job's)
    _plant_token_altered, end_to_end,
)
from benchmark.jobs.serve_latent_moe import _pad_to
from benchmark.jobs.serve_state_space import (  # noqa: F401  (the job's)
    release, window,
)

HERE = os.path.dirname(os.path.abspath(__file__))


def _apply_toy(ctx):
    with open(os.path.join(HERE, os.pardir,
                           "rehearsal_serve_linear_attention.json")) as f:
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
        raise ValueError("jobs/serve_linear_attention.py drives closed loops "
                         "only")
    job.undo = _plant_state_fault(ctx.fault)
    net = model_olmo_hybrid.install_weights(
        model_olmo_hybrid.build_network(cfg), cfg, ctx.seed)
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
    job.net = net
    if ctx.fault == "token_altered":
        _plant_token_altered(job.engine, job.vocab)
    job.engine.start()
    job.threads = [threading.Thread(target=job.client, daemon=True,
                                    name=f"client-{i}")
                   for i in range(tr["clients"])]
    for t in job.threads:
        t.start()
    time.sleep(tr["ramp_s"])


def token_gaps(cfg, seed, requests, pad_to, controls=()):
    """``jobs/serve_state_space.token_gaps`` against
    ``reference_olmo_hybrid``: ``{"f32": {number: value}, control: {...}}``
    and the count of tokens judged.  The reference runs once over every
    request's prompt + served tokens (one padded shape; causal and recurrent
    forward in time, so the padding behind a sequence is never seen), at
    ``f32`` and at each precision of ``controls``; the head over the served
    positions only, a block of them at a time."""
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
def _plant_state_fault(fault):
    """``state_not_reset``: a delta-rule layer never sees position 0, so a
    slot keeps its last tenant's state and tail.  ``padding_advances_state``:
    a delta-rule layer is not told how many of a bucket's tokens are real,
    so the padding moves the state.  Planted on the layer class while the
    programs are traced; returns what undoes it."""
    if fault not in ("state_not_reset", "padding_advances_state"):
        return ()
    from deeplearning4j_tpu.nn.layers import GatedDeltaNetLayer

    real = GatedDeltaNetLayer.apply_with_carry

    def faulty(self, params, state, x, carry, **kw):
        if isinstance(carry, dict):
            if fault == "state_not_reset":
                carry = {**carry, "pos": carry["pos"] + 1}
            else:
                carry = {k: v for k, v in carry.items() if k != "live"}
        return real(self, params, state, x, carry, **kw)

    GatedDeltaNetLayer.apply_with_carry = faulty
    return (lambda: setattr(GatedDeltaNetLayer, "apply_with_carry", real),)
