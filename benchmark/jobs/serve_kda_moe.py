"""Job ``serve_kda_moe``: ``jobs/serve.py``'s closed loop around a net that
``model_ling`` builds — Kimi Delta Attention layers on state slots beside a
latent attention layer on pages, and group-limited experts as one chip's
share — by import: the gauge ``dl4j_state_slots_in_use`` and the release
of ``jobs/serve_state_space.py``, the expert counters of
``jobs/serve_latent_moe.py`` (``moe_counts``), and the comparison, the
calibration and the state faults of ``jobs/serve_linear_attention.py``.

``correct``: ``serve_linear_attention``'s ``token_gaps`` / ``check`` /
``calibrate`` run against ``reference_ling`` (below: the same functions
over that module's globals with ``ref`` rebound, no copy): over every
served position of the sampled requests, the gap by which the served greedy
token's reference logit lies below the reference's best; the widest
(``served_logit_gap``) and the mean (``served_logit_gap_mean``).  The
reference runs KDA a position at a time from zero state and latent
attention expanded, so a slot that kept its last tenant's state, a state
that bucket padding advanced, or a latent page misread shows as a gap.

Faults (tests and the calibration only): ``token_altered`` (as
``serve_latent_moe`` plants it: an expert net's decode returns its counts
beside the token), ``state_not_reset``, ``padding_advances_state`` (as
``serve_linear_attention`` plants them),
and ``state_bf16``: the KDA state pool kept in bfloat16 (the decode step
then takes the ``jnp`` form, which rounds the state on every write).

``rehearsal.json`` has no place for a new job's toy sizes: under
``--rehearsal`` they come from ``benchmark/rehearsal_serve_kda_moe.json``.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import types

from benchmark import model_ling, reference_ling, traffic_gen
from benchmark.jobs import serve, serve_linear_attention, serve_state_space
from benchmark.jobs.serve import end_to_end  # noqa: F401  (the job's)
from benchmark.jobs.serve_latent_moe import _plant_token_altered, moe_counts
from benchmark.jobs.serve_state_space import release  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))


def _over_reference(fn, scope):
    """``fn`` as written in ``serve_linear_attention``, its globals
    ``scope``."""
    return types.FunctionType(fn.__code__, scope, fn.__name__,
                              fn.__defaults__, fn.__closure__)


_SCOPE = {**vars(serve_linear_attention), "ref": reference_ling}
token_gaps, check, calibrate = (
    _over_reference(getattr(serve_linear_attention, name), _SCOPE)
    for name in ("token_gaps", "check", "calibrate"))
_SCOPE["token_gaps"] = token_gaps


def _apply_toy(ctx):
    with open(os.path.join(HERE, os.pardir,
                           "rehearsal_serve_kda_moe.json")) as f:
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
        raise ValueError("jobs/serve_kda_moe.py drives closed loops only")
    net = model_ling.install_weights(model_ling.build_network(cfg), cfg,
                                     ctx.seed)
    job.undo = (_plant_state_bf16() if ctx.fault == "state_bf16"
                else serve_linear_attention._plant_state_fault(ctx.fault))
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
    if ctx.fault == "token_altered":
        _plant_token_altered(job.engine, job.vocab)
    job.engine.start()
    job.threads = [threading.Thread(target=job.client, daemon=True,
                                    name=f"client-{i}")
                   for i in range(tr["clients"])]
    for t in job.threads:
        t.start()
    time.sleep(tr["ramp_s"])


def window(ctx, seconds):
    """``serve_state_space.window`` (the gauge, in a traced run) inside a
    span that brackets the expert counters, as ``serve_latent_moe``'s."""
    job, span = ctx.state, ctx.window_span

    @contextlib.contextmanager
    def counted():
        before = moe_counts(job)
        with span():
            yield
        ctx.obs["moe_counts"] = moe_counts(job) - before

    ctx.window_span = counted
    serve_state_space.window(ctx, seconds)


# --------------------------------------------------- faults (tests only)
def _plant_state_bf16():
    """The KDA state pool stored in bfloat16: the layer's pools are made in
    bfloat16 and its decode step takes the ``jnp`` form (the kernel steps a
    float32 pool only).  Returns what undoes it."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import KimiDeltaAttentionLayer

    cls = KimiDeltaAttentionLayer
    saved = {name: cls.__dict__.get(name)
             for name in ("init_paged_cache", "path")}
    real_pool, real_path = cls.init_paged_cache, cls.path

    def pool(self, *a, **kw):
        out = real_pool(self, *a, **kw)
        return {**out, "sh": out["sh"].astype(jnp.bfloat16)}

    def path(self, t):
        p = real_path(self, t)
        return self.PATHS[0] if p == self.PATHS[2] else p

    def undo():
        for name, attr in saved.items():
            if attr is None:
                delattr(cls, name)
            else:
                setattr(cls, name, attr)

    cls.init_paged_cache, cls.path = pool, path
    return (undo,)
