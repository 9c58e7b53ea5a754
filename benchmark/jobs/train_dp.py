"""Job ``train_dp``: the chips of one host, data-parallel:
``DistributedNetwork(net, SyncTrainingMaster(mesh=default_mesh(devices=
the cell's)))`` — every chip holds the whole model and the optimizer's
state, takes its share of the step's sequences (one a chip in
``sc2-3b.train-dp4``), and the gradients are all-reduced inside the step.

``jobs/train.py``'s feed, loss tap, window, end-to-end number, release and
comparison are this job's too (by import).  The feed hands the master a
``DataSet`` whose ids and dense one-hot labels are already on the chips,
sharded over the data axis by one small jitted function, so the master's
``device_put`` moves nothing.  Under ``--rehearsal`` the toy traffic and
limits come from ``benchmark/rehearsal_train_dp.json``, and the mesh is
whatever devices the backend has, up to the cell's count.

``correct``: the first ``checked_steps`` steps against the plain reference's
on the whole batch, as in ``jobs/train.py`` — every step's loss, the norm of
every leaf's first gradient, the norm of every leaf's change.  The
reference's step over all the sequences does not fit one chip, so here it
takes its gradient a sequence at a time (the mean of the sequences'
gradients is the batch's) and then ``reference.train_step``'s own Adam.
"""

from __future__ import annotations

import json
import os
from functools import partial

import jax
import jax.numpy as jnp

from benchmark import model, reference, traffic_gen
from benchmark.jobs import train
from benchmark.jobs.train import (  # noqa: F401  (the job's)
    compare, end_to_end, window,
)


HERE = os.path.dirname(os.path.abspath(__file__))


class DpJob(train.TrainJob):
    def __init__(self, ctx):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deeplearning4j_tpu import backend
        from deeplearning4j_tpu.datasets.dataset import DataSet

        super().__init__(ctx)
        self.mesh = backend.default_mesh(devices=list(ctx.devices))
        chips = self.mesh.shape[backend.AXIS_DATA]
        if self.sequences % chips:
            raise ValueError(f"{self.sequences} sequences a step do not "
                             f"divide over {chips} chips")
        data = NamedSharding(self.mesh, P(backend.AXIS_DATA))
        vocab = self.vocab

        def one_hot(ids, keep=None):
            y = jax.nn.one_hot(ids[:, 1:], vocab, dtype=jnp.float32)
            return ids[:, :-1], y if keep is None else y * keep

        self._one_hot = one_hot
        self._sharded = lambda f: jax.jit(f, out_shardings=(data, data))
        batch = self._sharded(one_hot)
        self.to_batch = lambda ids: DataSet(*batch(ids))

    def fit(self, feed):
        self.dist.fit(feed)
        return feed


def _apply_toy(ctx):
    """``rehearsal.json`` gave the toy configuration; it has no place for a
    new job's toy traffic and limits."""
    with open(os.path.join(HERE, os.pardir, "rehearsal_train_dp.json")) as f:
        toy = json.load(f)
    ctx.traffic.update(toy["traffic"])
    ctx.limits.clear()
    ctx.limits.update(toy["limits"])


def setup(ctx):
    from deeplearning4j_tpu.parallel import (
        DistributedNetwork, SyncTrainingMaster,
    )

    if ctx.rehearsal:
        _apply_toy(ctx)
    job = ctx.state = DpJob(ctx)
    cfg, tr = ctx.config, ctx.traffic
    weights = reference.make_weights(cfg, ctx.seed)
    net = model.build_network(cfg, max_seq=job.seq_len,
                              updater=tr["optimizer"]["name"], lr=job.lr)
    model.install_weights(net, weights, job.layers, with_updater=True)
    del weights
    net.listeners.append(train.LossTap(job))
    job.net = net
    job.master = SyncTrainingMaster(mesh=job.mesh)
    job.dist = DistributedNetwork(net, job.master)
    if ctx.fault == "chip_left_out":
        _plant_chip_left_out(job)

    n = int(tr["checked_steps"])
    job.fit(train.Feed(job, 0, stop_after=1))        # compiles; step 1
    m = model.flat_leaves(net.updater_state["m"], job.layers)
    first = reference.leaf_norms(m)
    job.fit(train.Feed(job, 1, stop_after=n - 1))
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


def release(ctx):
    job = ctx.state
    if job.net is not None:
        job.master._step = None
        job.dist = job.master = None
    train.release(ctx)


# ------------------------------------------------------------ the reference
@partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(w, m, v, g, step, lr):
    """``reference.train_step``'s update, on a gradient already taken."""
    b1, b2 = reference.BETA1, reference.BETA2
    t = step.astype(jnp.float32) + 1.0
    new_w, new_m, new_v = {}, {}, {}
    for k in w:
        new_m[k] = b1 * m[k] + (1 - b1) * g[k]
        new_v[k] = b2 * v[k] + (1 - b2) * g[k] * g[k]
        mhat = new_m[k] / (1 - b1 ** t)
        vhat = new_v[k] / (1 - b2 ** t)
        new_w[k] = w[k] - lr * mhat / (jnp.sqrt(vhat) + reference.ADAM_EPS)
    return new_w, new_m, new_v


@partial(jax.jit, static_argnums=(2, 3))
def _sequence_grad(w, row, items, precision):
    return jax.value_and_grad(reference.loss_of)(
        w, row[None, :-1], row[None, 1:], dict(items), precision)


@partial(jax.jit, donate_argnums=(0,))
def _add(acc, g, scale):
    return jax.tree_util.tree_map(lambda a, b: a + scale * b, acc, g)


def _batch_grad(w, ids, items, precision, left_out):
    """(loss, gradient) of the batch ``ids`` [S, T + 1]: the mean over its
    sequences, taken one sequence at a time.  ``left_out`` plants the fault
    "one chip's gradient left out of the mean": the last of ``left_out``
    equal shares of the sequences is skipped, the mean taken over the
    rest."""
    rows = ids if not left_out else ids[:len(ids) - len(ids) // left_out]
    scale = 1.0 / len(rows)
    loss, acc = 0.0, jax.tree_util.tree_map(jnp.zeros_like, w)
    for row in rows:
        l, g = _sequence_grad(w, jnp.asarray(row), items, precision)
        loss += float(l) * scale
        acc = _add(acc, g, scale)
    return loss, acc


def reference_readings(cfg, traffic, seed, precision="f32", left_out=0):
    """The first steps as the plain reference takes them (or, for the
    control and the planted fault, the reference in the program's place)."""
    n = int(traffic["checked_steps"])
    seqs, t = traffic["sequences_per_step"], traffic["seq_len"]
    lr = float(traffic["optimizer"]["learning_rate"])
    items = reference.cfg_items(cfg)
    w = reference.make_weights(cfg, seed)
    m = {k: jnp.zeros_like(a) for k, a in w.items()}
    v = {k: jnp.zeros_like(a) for k, a in w.items()}
    losses, first = [], None
    for step in range(n):
        ids = traffic_gen.train_ids(seed, step, seqs, t, cfg["vocab_size"])
        loss, g = _batch_grad(w, ids, items, precision, left_out)
        if step == 0:
            first = {k: float(x) for k, x in
                     jax.device_get(reference.leaf_norms(g)).items()}
        w, m, v = _adam(w, m, v, g, jnp.asarray(step), lr)
        del g
        losses.append(loss)
    del m, v
    start = reference.make_weights(cfg, seed)
    change = {k: float(x) for k, x in
              jax.device_get(reference.change_norms(w, start)).items()}
    del w, start
    return {"loss": losses, "grad_norm": first, "change_norm": change}


def check(ctx):
    job = ctx.state
    ref = reference_readings(ctx.config, ctx.traffic, ctx.seed)
    gaps = compare(job.readings, ref)
    ctx.obs.setdefault("notes", {})["reference_loss"] = ref["loss"]
    ctx.obs["notes"]["program_loss"] = job.readings["loss"]
    ctx.obs["notes"]["worst_leaf"] = gaps["worst"]
    ctx.obs["notes"]["chips"] = len(ctx.devices)
    return [(name, gaps[name], ctx.limits[name]) for name in ctx.limits]


def calibrate(ctx, with_control):
    """The readings a limit is set from (``benchmark/calibrate.py``): the
    program's gaps; and, with ``with_control``, the gaps of the reference put
    in the program's place at bfloat16 (a second witness), at fp8 (the
    control) and with one chip's share of the batch left out of the mean
    (the fault)."""
    job = ctx.state
    chips = len(ctx.devices)
    ref = reference_readings(ctx.config, ctx.traffic, ctx.seed)
    out = {"program": compare(job.readings, ref)}
    if with_control:
        for name, kw in (("reference_bf16", {"precision": "bf16"}),
                         ("control_fp8", {"precision": "fp8"}),
                         ("fault_chip_left_out",
                          {"left_out": max(chips, 2)})):
            got = reference_readings(ctx.config, ctx.traffic, ctx.seed, **kw)
            out[name] = compare(got, ref)
    return out


# --------------------------------------------------- faults (tests only)
def _plant_chip_left_out(job):
    """One chip's gradient left out of the mean: the last chip's sequences
    carry no label, and the loss is rescaled to the mean over the rest."""
    from deeplearning4j_tpu import backend
    from deeplearning4j_tpu.datasets.dataset import DataSet

    chips = max(job.mesh.shape[backend.AXIS_DATA], 2)
    kept = job.sequences - job.sequences // chips
    keep = (jnp.arange(job.sequences) < kept)[:, None, None] * (
        job.sequences / kept)
    batch = job._sharded(lambda ids: job._one_hot(ids, keep))
    job.to_batch = lambda ids: DataSet(*batch(ids))
