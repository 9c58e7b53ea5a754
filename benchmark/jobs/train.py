"""Job ``train``: one chip, ``MultiLayerNetwork.fit(iterator)``.

Set-up builds one network with its compiled step, installs the seed's
weights, and drives it through its first ``checked_steps`` steps with the
window's own call and feed; the same object then runs the window.  The
feed sends int32 ids and builds the dense one-hot labels the program's
loss wants on the device, in a small jitted function of its own (a
[2, 4096, 49152] float32 array made on the host would be most of a step).

``correct`` follows those first steps with the plain reference: every
step's loss, the norm of every leaf's first gradient as the optimizer got
it (Adam's first moment after one step is (1 - beta1) * g), and the norm
of every leaf's change after the steps.
"""

from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import model, reference, traffic_gen

class Feed:
    """The iterator ``fit`` consumes: batches ``first .. `` until
    ``stop_after`` steps or the deadline.  Before handing out batch k it
    waits for the loss of step k - 1 - in_flight, so the host runs at most
    ``in_flight`` steps ahead of the device (a bounded prefetch)."""

    def __init__(self, job, first, stop_after=None, deadline=None):
        self.job, self.step = job, first
        self.stop_at = None if stop_after is None else first + stop_after
        self.deadline = deadline
        self.served = 0

    def __iter__(self):
        return self

    def __next__(self):
        job = self.job
        if self.stop_at is not None and self.step >= self.stop_at:
            raise StopIteration
        while len(job.losses) > job.in_flight:
            job.losses.popleft().block_until_ready()
        if self.deadline is not None and time.perf_counter() >= self.deadline:
            raise StopIteration
        with jax.profiler.TraceAnnotation("feed_batch"):
            ids = traffic_gen.train_ids(job.seed, self.step, job.sequences,
                                        job.seq_len, job.vocab)
            batch = job.to_batch(ids)
        self.step += 1
        self.served += 1
        return batch


class LossTap:
    """A listener (the program's own hook): keeps each step's on-device
    loss, for the feed's bounded run-ahead and for the checked steps."""

    def __init__(self, job):
        self.job = job

    def iteration_done(self, net, iteration):
        loss = net._score
        self.job.losses.append(loss)
        self.job.all_losses.append(loss)


class TrainJob:
    def __init__(self, ctx):
        cfg, tr = ctx.config, ctx.traffic
        self.seed = ctx.seed
        self.layers = cfg["num_hidden_layers"]
        self.seq_len = tr["seq_len"]
        self.sequences = tr["sequences_per_step"]
        self.vocab = cfg["vocab_size"]
        self.in_flight = tr["steps_in_flight"]
        self.lr = tr["optimizer"]["learning_rate"]
        self.losses = collections.deque()
        self.all_losses = []
        vocab = self.vocab

        @jax.jit
        def to_batch(ids):
            return ids[:, :-1], jax.nn.one_hot(ids[:, 1:], vocab,
                                               dtype=jnp.float32)
        self.to_batch = to_batch

    def fit(self, feed):
        self.net.fit(feed)
        return feed


def setup(ctx):
    job = ctx.state = TrainJob(ctx)
    cfg, tr = ctx.config, ctx.traffic
    weights = reference.make_weights(cfg, ctx.seed)
    net = model.build_network(cfg, max_seq=job.seq_len,
                              updater=tr["optimizer"]["name"], lr=job.lr)
    model.install_weights(net, weights, job.layers, with_updater=True)
    del weights
    net.listeners.append(LossTap(job))
    job.net = net
    if ctx.fault == "state_unchanged":
        _plant_state_unchanged(net)
    if ctx.fault == "half_batch":
        _plant_half_batch(job)

    n = int(tr["checked_steps"])
    job.fit(Feed(job, 0, stop_after=1))              # compiles; step 1
    m = model.flat_leaves(net.updater_state["m"], job.layers)
    first = reference.leaf_norms(m)
    job.fit(Feed(job, 1, stop_after=n - 1))
    start = reference.make_weights(cfg, ctx.seed)
    change = reference.change_norms(
        model.flat_leaves(net.params, job.layers), start)
    del start, m
    job.readings = {
        "loss": [float(x) for x in job.all_losses[:n]],
        "grad_norm": {k: float(v) / (1 - reference.BETA1)
                      for k, v in jax.device_get(first).items()},
        "change_norm": {k: float(v)
                        for k, v in jax.device_get(change).items()}}
    job.next_step = n
    job.losses.clear()


def window(ctx, seconds):
    job = ctx.state
    with ctx.window_span():
        t0 = time.perf_counter()
        feed = job.fit(Feed(job, job.next_step, deadline=t0 + seconds))
        jax.block_until_ready(job.net._score)
        t1 = time.perf_counter()
    job.losses.clear()
    tokens = feed.served * job.sequences * job.seq_len
    last = float(job.net._score)
    ctx.obs.update(
        window_s=t1 - t0, steps=feed.served, tokens=tokens,
        attempted=feed.served,
        failed=0 if np.isfinite(last) else feed.served,
        notes={"steps": feed.served, "last_loss": last,
               "sequences_per_step": job.sequences, "seq_len": job.seq_len})


def end_to_end(ctx):
    return {"train_tokens_per_s": ctx.obs["tokens"] / ctx.obs["window_s"]}


def release(ctx):
    job = ctx.state
    net = job.net
    if net is None:
        return
    net.params, net.updater_state, net.net_state = {}, {}, {}
    net._jit_cache.clear()
    net._score = None
    job.net = None
    job.losses.clear()
    job.all_losses.clear()
    gc.collect()


def reference_readings(cfg, traffic, seed, precision="f32", fault=None):
    """The first steps as the plain reference takes them (or, for the
    control and the planted faults, the reference in the program's place)."""
    n = int(traffic["checked_steps"])
    seqs, t = traffic["sequences_per_step"], traffic["seq_len"]
    lr = float(traffic["optimizer"]["learning_rate"])
    items = reference.cfg_items(cfg)
    w = reference.make_weights(cfg, seed)
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    rows = None
    if fault == "half_batch":
        rows = jnp.asarray(np.arange(seqs * t).reshape(seqs, t)
                           < seqs * t // 2)
    losses, first = [], None
    for step in range(n):
        ids = traffic_gen.train_ids(seed, step, seqs, t, cfg["vocab_size"])
        w, m, v, loss, gn = reference.train_step(
            w, m, v, jnp.asarray(step), ids[:, :-1], ids[:, 1:], items, lr,
            precision, rows)
        losses.append(float(loss))
        if step == 0:
            first = {k: float(x) for k, x in jax.device_get(gn).items()}
    del m, v
    start = reference.make_weights(cfg, seed)
    change = {k: float(x) for k, x in
              jax.device_get(reference.change_norms(w, start)).items()}
    del w, start
    return {"loss": losses, "grad_norm": first, "change_norm": change}


def compare(got, ref):
    """The numbers compared.  Norms are compared by the worst leaf: the gap
    between the two norms over the reference's norm of that leaf or of the
    median leaf, whichever is larger.  Leaves whose reference gradient is
    under a thousandth of the median leaf's (a key's bias under softmax)
    move under Adam by round-off alone and are left out of the change."""
    out = {"loss_gap": max(abs(a - b) / abs(b)
                           for a, b in zip(got["loss"], ref["loss"]))}
    g_ref = ref["grad_norm"]
    g_med = float(np.median(list(g_ref.values())))
    g_gap = {k: abs(got["grad_norm"][k] - g_ref[k]) / max(g_ref[k], g_med)
             for k in g_ref}
    out["grad_norm_gap"] = max(g_gap.values())
    moved = [k for k in g_ref if g_ref[k] >= 1e-3 * g_med]
    c_ref = ref["change_norm"]
    c_med = float(np.median([c_ref[k] for k in moved]))
    c_gap = {k: abs(got["change_norm"][k] - c_ref[k]) / max(c_ref[k], c_med)
             for k in moved}
    out["change_norm_gap"] = max(c_gap.values())
    out["worst"] = {"grad_norm_gap": max(g_gap, key=g_gap.get),
                    "change_norm_gap": max(c_gap, key=c_gap.get),
                    "change_norm_gap_median": float(np.median(list(c_gap.values()))),
                    "left_out": sorted(set(g_ref) - set(moved))}
    return out


def check(ctx):
    job = ctx.state
    ref = reference_readings(ctx.config, ctx.traffic, ctx.seed)
    gaps = compare(job.readings, ref)
    ctx.obs.setdefault("notes", {})["reference_loss"] = ref["loss"]
    ctx.obs["notes"]["program_loss"] = job.readings["loss"]
    ctx.obs["notes"]["worst_leaf"] = gaps["worst"]
    return [(name, gaps[name], ctx.limits[name]) for name in ctx.limits]


def calibrate(ctx, with_control):
    """The readings a limit is set from (``benchmark/calibrate.py``): the
    program's gaps; and, with ``with_control``, the gaps of the reference
    put in the program's place at bfloat16 (a second witness of where a
    sound program reads), at fp8 (the control) and with half of the batch
    left out (the fault)."""
    job = ctx.state
    ref = reference_readings(ctx.config, ctx.traffic, ctx.seed)
    out = {"program": compare(job.readings, ref)}
    if with_control:
        for name, kw in (("reference_bf16", {"precision": "bf16"}),
                         ("control_fp8", {"precision": "fp8"}),
                         ("fault_half_batch", {"fault": "half_batch"})):
            got = reference_readings(ctx.config, ctx.traffic, ctx.seed, **kw)
            out[name] = compare(got, ref)
    return out


# --------------------------------------------------- faults (tests only)
def _plant_state_unchanged(net):
    """A step that returns its state unchanged."""
    real = net._get_train_step()

    def step(params, upd, state, *rest):
        keep = jax.tree_util.tree_map(jnp.copy, (params, upd, state))
        out = real(params, upd, state, *rest)
        return keep + tuple(out[3:])
    net._jit_cache[("train_step", False)] = step


def _plant_half_batch(job):
    """Half of the batch left out, the mean taken over the rest: the
    second half of the rows carries no label, and the loss is rescaled."""
    vocab = job.vocab

    @jax.jit
    def to_batch(ids):
        y = jax.nn.one_hot(ids[:, 1:], vocab, dtype=jnp.float32)
        b, t = ids.shape[0], ids.shape[1] - 1
        keep = (jnp.arange(b * t).reshape(b, t) < b * t // 2)
        return ids[:, :-1], y * keep[..., None] * 2.0
    job.to_batch = to_batch
