"""Distributed training strategy SPI + masters — the Spark scaleout redesign.

Reference: ``spark/dl4j-spark/.../api/TrainingMaster.java:27`` (strategy
object owning "how fit() distributes") and
``impl/paramavg/ParameterAveragingTrainingMaster.java:336-366,628-645``
(driver-centric: broadcast params -> executors train avgFreq minibatches ->
RDD.aggregate tree-reduce -> divide -> repeat).

TPU-native redesign: the driver never touches per-step data.  Training is
in-graph SPMD over a ``jax.sharding.Mesh`` spanning all chips (multi-host:
same code after ``jax.distributed.initialize`` — the mesh covers every
process's local devices and XLA routes collectives over ICI within a slice
and DCN across slices).  Two strategies:

- ``SyncTrainingMaster`` — synchronous DP: ONE jitted step per global batch;
  params replicated, batch sharded over the 'data' axis; the gradient
  all-reduce is inserted by XLA because the loss averages over the sharded
  batch.  This is the "modern" path and the perf-bench path: gradient sync
  costs one all-reduce per step riding ICI.
- ``ParameterAveragingTrainingMaster`` — reproduces the reference's
  averaging semantics (train ``averaging_frequency`` local minibatches per
  worker, then average params and optionally updater state), for capability
  parity and the distributed-vs-local equivalence tests
  (``TestCompareParameterAveragingSparkVsSingleMachine``).

The ``TrainingMaster`` SPI is kept as the strategy seam, like the reference.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.backend import device as backend
from deeplearning4j_tpu.helpers import auto_partitioned
from deeplearning4j_tpu.observability import (
    PhaseTimers, WorkerTelemetry, crash_dump, instrument, step_guard,
)
from deeplearning4j_tpu.observability import shardstats
from deeplearning4j_tpu.optimize import updaters as upd
from deeplearning4j_tpu.parallel import zero as zero_mod
from deeplearning4j_tpu.parallel.elastic import ElasticConfig, ElasticController


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bring-up (reference: Spark cluster + broadcast;
    here: jax.distributed — one call per host, then every jit spans the
    global mesh)."""
    jax.distributed.initialize(coordinator_address, num_processes, process_id)


class PhaseStats(PhaseTimers):
    """Phase-timed distributed training stats (≙ ``CommonSparkTrainingStats
    .java`` / ``ParameterAveragingTrainingMasterStats.java``).

    Since the unified-telemetry refactor this is a thin alias over
    ``observability.PhaseTimers``: the ``phase()`` / ``steps`` /
    ``as_dict()`` surface is unchanged, but every timed phase ALSO lands in
    the process-wide metrics registry as
    ``dl4j_phase_seconds{component=..., phase=...}`` so /metrics scrapes
    and bench snapshots see it (migration notes: docs/observability.md)."""

    def __init__(self, enabled: bool = True,
                 component: str = "training_master"):
        super().__init__(component, enabled=enabled)


class TrainingMaster:
    """Strategy SPI (reference ``TrainingMaster.java:27``)."""

    def execute_training(self, net, iterator) -> None:
        raise NotImplementedError

    def training_stats(self) -> Dict[str, Any]:
        return {}


class SyncTrainingMaster(TrainingMaster):
    """Per-step synchronous data parallelism over the mesh.

    Each global batch of size B is sharded into B/K per-device shards; the
    jitted step computes local grads and XLA all-reduces them (mean over the
    global batch) before the updater applies — one collective per step.
    """

    def __init__(self, mesh: Optional[Mesh] = None, batch_size: Optional[int] = None,
                 prefetch_size: int = 2, collect_stats: bool = False,
                 checkpoint_manager=None, retry_policy=None, elastic=False,
                 update_sharding: str = zero_mod.REPLICATED):
        self.mesh = mesh or backend.default_mesh()
        self.batch_size = batch_size
        self.prefetch_size = prefetch_size
        self.collect_stats = collect_stats
        # ZeRO update sharding (arXiv 2004.13336, docs/PARALLELISM.md
        # "ZeRO"): with update_sharding="zero" the gradients are
        # reduce-scattered instead of all-reduced, each device updates
        # only its 1/K shard of the params + updater state, and the
        # params are all-gathered for the next forward — same wire
        # bytes, 1/K the persistent optimizer memory.  Default
        # "replicated" keeps today's all-reduce + replicated update.
        self.update_sharding = zero_mod.validate_mode(update_sharding,
                                                      self.mesh)
        self._zero_layout = (zero_mod.ZeroLayout(self.mesh)
                             if self.update_sharding == zero_mod.ZERO
                             else None)
        # elasticity (docs/resilience.md "Elasticity"): a dead/hung/
        # straggling data shard is evicted by zeroing its rows in the
        # labels mask — the masked loss mean renormalizes over the healthy
        # rows (losses.score divides by sum(mask)), so the gradient is the
        # DeepSpark-style average over the degraded worker set.  Params
        # stay replicated, so re-admission needs no catch-up: the mask
        # just flips back.  Pass True or an ElasticConfig.
        self._elastic: Optional[ElasticController] = None
        if elastic is not False and elastic is not None:
            ecfg = elastic if isinstance(elastic, ElasticConfig) else ElasticConfig()
            self.collect_stats = True        # straggler verdicts need stats
            slots = self._data_slot_devices()
            self._elastic = ElasticController(
                "sync_master", [f"d{s[0].id}" for s in slots], config=ecfg,
                aliases={f"d{s[0].id}": [f"d{d.id}" for d in s]
                         for s in slots})
        # resilience wiring (docs/resilience.md): auto-resume on entry,
        # boundary saves, clean preemption stop, transient step retry
        self.checkpoint_manager = checkpoint_manager
        self.retry_policy = retry_policy
        # step_time_ms is a bounded window (last 1024) — stats stay O(1)
        # however long training runs; PhaseStats carries the full aggregates
        self._stats: Dict[str, Any] = {
            "steps": 0, "step_time_ms": collections.deque(maxlen=1024)}
        # per-step phase timers only when stats collection is requested —
        # the default hot loop stays timer-free.  Phase mapping vs the
        # reference: fetch≙split/repartition, place≙broadcast, dispatch =
        # gradient compute + the in-graph all-reduce (the reference's
        # aggregate), device_sync = host sync on the step result.
        self._phases = PhaseStats(enabled=collect_stats,
                                  component="sync_master")
        # per-device step time (published only under collect_stats — the
        # per-shard arrival measurement IS a device sync, which that mode
        # already pays in its device_sync phase)
        self._workers: Optional[WorkerTelemetry] = None
        self._step = None
        self._stab_rt = None          # StabilityRuntime (net.conf.stability)
        self._stab_workers: list = []  # data-slot worker ids ("d<id>")

    @property
    def elastic(self) -> Optional[ElasticController]:
        """The elasticity state machine (None unless ``elastic=`` was
        passed) — ``elastic.summary()`` is the operator view."""
        return self._elastic

    def _data_slot_devices(self):
        """Devices grouped by data-axis slot: ``order[k]`` is EVERY device
        holding slot ``k`` of the [K]-sharded batch (one on a pure-DP
        mesh, model*seq of them on a composed mesh).  The first member
        names the slot (``d<id>``) for the elastic controller; the rest
        become its aliases, so telemetry verdicts and injected faults on
        ANY member evict the whole slot."""
        K = self.mesh.shape[backend.AXIS_DATA]
        sh = NamedSharding(self.mesh, P(backend.AXIS_DATA))
        order = [[] for _ in range(K)]
        # the GLOBAL device map: on a multi-host mesh the addressable map
        # only covers this host's devices, which would leave remote hosts'
        # slots empty (and slot naming must agree across processes anyway)
        for dev, idx in sh.devices_indices_map((K,)).items():
            sl = idx[0] if idx else slice(None)
            for i in range(*sl.indices(K)):
                order[i].append(dev)
        for slot in order:
            slot.sort(key=lambda d: d.id)
        return order

    def _evicted_labels_mask(self, ds, emask, K: int):
        """Labels mask with the evicted data slots' rows zeroed (existing
        mask respected).  The masked score normalizes by ``sum(mask)``, so
        zeroed rows renormalize the global gradient mean over the healthy
        rows — eviction without touching the compiled collective."""
        B = len(ds)
        rw = np.repeat(np.asarray(emask, np.float32), B // K)
        lm = ds.labels_mask
        if lm is None:
            return rw.reshape((B,) + (1,) * (ds.labels.ndim - 2))
        lm = np.asarray(lm)
        return lm * rw.reshape((B,) + (1,) * (lm.ndim - 1))

    def _param_layout(self, net):
        """Sharding (single or per-param pytree) for the parameters.  Base:
        fully replicated.  TensorParallelTrainingMaster overrides this with
        model-axis shardings — the jitted step is otherwise identical."""
        return NamedSharding(self.mesh, P())

    def _build(self, net):
        from deeplearning4j_tpu.observability import introspection, numerics
        from deeplearning4j_tpu.resilience import stability

        cfg = net.conf.updater
        policy = net.conf.stability
        plan = introspection.plan_for(net)
        nplan = numerics.plan_for(net)
        lr_overrides = {
            l.name: l.learning_rate for l in net.layers if l.learning_rate is not None
        }
        mesh = self.mesh
        K = mesh.shape[backend.AXIS_DATA]
        repl = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P(backend.AXIS_DATA))
        players = self._param_layout(net)
        # updater state mirrors the param tree per slot ({"m": ..., "v": ...})
        # but only over TRAINABLE layers — restrict to the state's own keys.
        # The stability, introspection and numerics subtrees are plain
        # scalars/small vectors: replicated, like the rest of the non-param
        # step state.
        if isinstance(players, dict) and net.updater_state:
            ulayers: Any = {
                slot: (repl if slot in (stability.STATE_KEY,
                                        introspection.STATE_KEY,
                                        numerics.STATE_KEY)
                       else {ln: players[ln] for ln in tree})
                for slot, tree in net.updater_state.items()
            }
        elif isinstance(players, dict):
            ulayers = repl
        else:
            ulayers = players

        def step(params, upd_state, net_state, iteration, x, y, rng, fm, lm):
            nstate = None
            if nplan is not None:
                nstate, upd_state = numerics.split_state(upd_state)
            if plan is not None:
                _, upd_state = introspection.split_state(upd_state)
            now = numerics.collect_now(nplan, iteration)
            kw = ({"collect_acts": True}
                  if numerics.wants_acts(plan, nplan) else {})
            if kw and now is not None:
                kw["numerics_now"] = now
            if policy is None:
                (loss, aux), grads = jax.value_and_grad(net._loss_fn, has_aux=True)(
                    params, net_state, x, y, rng, fm, lm, None, **kw
                )
                new_ns, _, act_stats = numerics.unpack_aux(plan, nplan, aux)
                grads = {k: v for k, v in grads.items() if v}
                updates, new_us = upd.update(cfg, grads, upd_state, iteration,
                                             lr_overrides, params=params)
                new_params = {
                    ln: (upd.apply_updates(params[ln], u)
                         if (u := updates.get(ln)) else params[ln])
                    for ln in params
                }
                # the gradients here are already the all-reduced global
                # mean, so the per-layer norms are the cluster-wide view
                # (replicated across devices)
                introspection.attach(
                    new_us, plan, grads=grads, params=params,
                    new_params=new_params, iteration=iteration,
                    act_stats=act_stats)
                numerics.attach(
                    new_us, nplan, grads=grads, iteration=iteration,
                    act_stats=act_stats, prev=nstate, now=now)
                return new_params, new_us, new_ns, loss
            # stability engine (resilience/stability.py): poisoned ROWS are
            # zeroed before the forward (NaN activations poison the
            # backward even under a zero cotangent) and renormalized out
            # of the masked loss mean — the global gradient is EXACTLY the
            # mean over the healthy rows, the sync-master analog of the
            # wrapper's [K] weight mask.  A residual non-finite verdict
            # (fp overflow in healthy data) still skips the whole step
            # device-side.  The caller guarantees lm is always an array
            # (all-ones when no mask), so poison flips values, not the
            # pytree — zero recompiles.
            stab, inner = stability.split_state(upd_state)
            row_ok = stability.finite_rows(x, y)
            x = stability.zero_nonfinite_rows(x, row_ok)
            y = stability.zero_nonfinite_rows(y, row_ok)
            lm = lm * row_ok.reshape((row_ok.shape[0],)
                                     + (1,) * (lm.ndim - 1))
            (_, (loss, aux)), grads = jax.value_and_grad(
                stability.scaled_loss(net._loss_fn, stab), has_aux=True)(
                params, net_state, x, y, rng, fm, lm, None, **kw)
            new_ns, _, act_stats = numerics.unpack_aux(plan, nplan, aux)
            # an all-rows-poisoned batch yields a zero loss and zero
            # gradients — finite, but updating would still decay Adam
            # moments toward the pad; veto it
            new_params, new_us, new_ns, _ = stability.apply_guarded_update(
                policy, cfg, stab, inner, params, net_state, loss, grads,
                new_ns, iteration, lr_overrides,
                extra_ok=jnp.sum(row_ok) > 0)
            introspection.attach(
                new_us, plan, grads=grads, params=params,
                new_params=new_params, iteration=iteration,
                act_stats=act_stats, grad_scale=1.0 / stab["loss_scale"])
            numerics.attach(
                new_us, nplan, grads=grads, iteration=iteration,
                act_stats=act_stats, grad_scale=1.0 / stab["loss_scale"],
                prev=nstate, now=now)
            return (new_params, new_us, new_ns, loss,
                    stability.slot_poison_flags(row_ok, K))

        in_shardings = (players, ulayers, repl, repl, data, data, repl, data,
                        data)
        out_shardings = (players, ulayers, repl, repl)
        if policy is not None:
            out_shardings = out_shardings + (repl,)
        # XLA partitions this program from the shardings; the Pallas
        # helpers inside need to be told (helpers.auto_partitioned)
        self._step = instrument(jax.jit(
            auto_partitioned(mesh)(step),
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=(0, 1, 2),
        ), f"{type(self).__name__}.step", argnums=(3, 4, 5, 6, 7, 8))
        self._data_sharding = data
        self._repl_sharding = repl
        self._params_layout = players
        self._upd_layout = ulayers

    def _build_zero(self, net):
        """The ZeRO-sharded step (update_sharding="zero"): forward +
        backward run per data shard inside a ``shard_map`` — each device
        all-gathers the sharded params, computes its LOCAL gradient
        contribution (the per-shard loss weighted by that shard's share
        of the global normalizer, so the psum of contributions is
        exactly the replicated step's global-mean gradient, masked
        normalization and regularization included), and reduce-scatters
        it — then the updater, the stability guard and introspection run
        UNCHANGED on the sharded trees under GSPMD (per-layer
        normalization norms and finiteness reductions come out global
        automatically).  Params and Adam moments live sharded; the
        ``__stability__`` / ``__introspect__`` subtrees stay replicated
        (the choice is recorded in the sharding ledger's notes).  The
        ``__numerics__`` precision-ledger subtree is carried through
        UNCHANGED (stale): its max-abs / fraction stats do not merge
        correctly across per-shard activation views (a pmean of
        per-shard maxes is not the global max), so harvest reports the
        last non-ZeRO refresh (docs/observability.md "Numerics")."""
        from deeplearning4j_tpu.observability import introspection, numerics
        from deeplearning4j_tpu.resilience import stability

        if type(self)._param_layout is not SyncTrainingMaster._param_layout:
            raise ValueError(
                "update_sharding='zero' composes only with the base "
                "data-parallel param layout (replicated); "
                f"{type(self).__name__} overrides _param_layout")
        cfg = net.conf.updater
        policy = net.conf.stability
        plan = introspection.plan_for(net)
        lr_overrides = {
            l.name: l.learning_rate for l in net.layers
            if l.learning_rate is not None
        }
        mesh = self.mesh
        K = mesh.shape[backend.AXIS_DATA]
        layout = self._zero_layout
        repl = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P(backend.AXIS_DATA))
        players = layout.tree_shardings(net.params)
        ulayers: Any = (layout.upd_shardings(net.updater_state)
                        if net.updater_state else repl)
        pmask = layout.mask(net.params)
        p_specs = layout.tree_specs(net.params)
        kw = ({"collect_acts": True}
              if plan is not None and plan.collect_acts else {})
        AX = zero_mod.AXIS

        def step(params, upd_state, net_state, iteration, x, y, rng, fm, lm):
            num_held, upd_state = numerics.split_state(upd_state)
            if plan is not None:
                _, upd_state = introspection.split_state(upd_state)
            if policy is not None:
                stab, inner = stability.split_state(upd_state)
                row_ok = stability.finite_rows(x, y)
                x = stability.zero_nonfinite_rows(x, row_ok)
                y = stability.zero_nonfinite_rows(y, row_ok)
                lm = lm * row_ok.reshape((row_ok.shape[0],)
                                         + (1,) * (lm.ndim - 1))
                scale = stab["loss_scale"]
            else:
                stab, inner = None, upd_state
                scale = jnp.ones((), jnp.float32)
            has_fm = fm is not None

            def local(p_blk, ns, xb, yb, rngb, lmb, sc, *rest):
                fmb = rest[0] if has_fm else None
                p_full = zero_mod.all_gather_tree(p_blk, pmask)
                # this shard's share of the global normalizer: the
                # per-shard loss is sum/max(sum(mask),1) + reg, so
                # weighting it by sum(mask_shard)/psum(sum(mask)) makes
                # the psum of weighted losses the exact global masked
                # mean + reg (a fully-masked shard contributes 0, and
                # the reg term's weights sum to 1)
                denom = jnp.sum(lmb.astype(jnp.float32))
                n_total = lax.psum(denom, AX)
                w = jnp.where(n_total > 0,
                              denom / jnp.maximum(n_total, 1.0), 0.0)

                def weighted_loss(p, n):
                    loss, aux = net._loss_fn(p, n, xb, yb, rngb, fmb, lmb,
                                             None, **kw)
                    return loss * (w * sc), (loss, aux)

                (_, (loss_raw, aux)), g = jax.value_and_grad(
                    weighted_loss, has_aux=True)(p_full, ns)
                new_ns, _, act_stats = introspection.unpack_aux(plan, aux)
                gloss = lax.psum(loss_raw * w, AX)
                g_sh = zero_mod.reduce_scatter_tree(g, K)
                # per-shard batch statistics averaged into the
                # replicated net state (batch-norm caveat:
                # docs/PARALLELISM.md "ZeRO")
                new_ns = jax.tree_util.tree_map(
                    lambda a: lax.pmean(a, AX), new_ns)
                if act_stats is not None:
                    act_stats = jax.tree_util.tree_map(
                        lambda a: lax.pmean(a, AX), act_stats)
                    return g_sh, gloss, new_ns, act_stats
                return g_sh, gloss, new_ns

            g_specs = jax.tree_util.tree_map(
                lambda m: P(AX) if m else P(), pmask)
            in_specs = (p_specs, P(), P(AX), P(AX), P(), P(AX), P()) \
                + ((P(AX),) if has_fm else ())
            out_specs = (g_specs, P(), P()) \
                + ((P(),) if kw else ())
            args = (params, net_state, x, y, rng, lm, scale) \
                + ((fm,) if has_fm else ())
            out = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)(*args)
            if kw:
                g_sh, gloss, new_ns, act_stats = out
            else:
                (g_sh, gloss, new_ns), act_stats = out, None
            g_sh = {k: v for k, v in g_sh.items() if v}
            if policy is None:
                updates, new_us = upd.update(cfg, g_sh, inner, iteration,
                                             lr_overrides, params=params)
                new_params = {
                    ln: (upd.apply_updates(params[ln], u)
                         if (u := updates.get(ln)) else params[ln])
                    for ln in params
                }
                introspection.attach(
                    new_us, plan, grads=g_sh, params=params,
                    new_params=new_params, iteration=iteration,
                    act_stats=act_stats)
                if num_held is not None:
                    # stale carry-through (see the docstring)
                    new_us[numerics.STATE_KEY] = num_held
                return new_params, new_us, new_ns, gloss
            # guarded tail on the SHARDED trees: the all-poisoned-batch
            # veto and the device-side skip mask work unchanged (the
            # finiteness reductions over sharded leaves are global)
            new_params, new_us, new_ns, _ = stability.apply_guarded_update(
                policy, cfg, stab, inner, params, net_state, gloss, g_sh,
                new_ns, iteration, lr_overrides,
                extra_ok=jnp.sum(row_ok) > 0)
            introspection.attach(
                new_us, plan, grads=g_sh, params=params,
                new_params=new_params, iteration=iteration,
                act_stats=act_stats, grad_scale=1.0 / stab["loss_scale"])
            if num_held is not None:
                # stale carry-through (see the docstring)
                new_us[numerics.STATE_KEY] = num_held
            return (new_params, new_us, new_ns, gloss,
                    stability.slot_poison_flags(row_ok, K))

        in_shardings = (players, ulayers, repl, repl, data, data, repl,
                        data, data)
        out_shardings = (players, ulayers, repl, repl)
        if policy is not None:
            out_shardings = out_shardings + (repl,)
        self._step = instrument(jax.jit(
            step,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=(0, 1, 2),
        ), f"{type(self).__name__}.step_zero", argnums=(3, 4, 5, 6, 7, 8))
        self._data_sharding = data
        self._repl_sharding = repl
        self._params_layout = players
        self._upd_layout = ulayers

    def execute_training(self, net, iterator):
        from deeplearning4j_tpu.datasets.iterator import AsyncDataSetIterator, DataSetIterator
        from deeplearning4j_tpu.models.common import notify_listeners
        from deeplearning4j_tpu.resilience import (
            FitResilience, get_fault_injector, preemption_requested,
        )

        res = None
        if self.checkpoint_manager is not None or self.retry_policy is not None:
            # resume BEFORE device placement so restored leaves get their
            # saved PartitionSpecs over this master's mesh
            res = FitResilience("sync_master", self.checkpoint_manager,
                                self.retry_policy, net=net, mesh=self.mesh)
        if isinstance(iterator, DataSetIterator) and iterator.async_supported():
            iterator = AsyncDataSetIterator(iterator, self.prefetch_size)
        policy = net.conf.stability
        if policy is not None:
            from deeplearning4j_tpu.resilience import stability

            # stability state must exist BEFORE device placement so the
            # guard/scale scalars ride in upd_state under _upd_layout
            stability.ensure_state(net)
            created = self._stab_rt is None
            if created:
                slots = self._data_slot_devices()
                self._stab_workers = [f"d{s[0].id}" for s in slots]
                self._stab_rt = stability.StabilityRuntime(
                    "sync_master", policy, worker_ids=self._stab_workers)
            if created or (res is not None and res.resumed_from is not None):
                # a restored nonfinite_total is history, not fresh evidence
                self._stab_rt.baseline_from(
                    net.updater_state.get(stability.STATE_KEY))
        stab_rt = self._stab_rt
        introspect = getattr(net.conf, "introspection", None) is not None
        if introspect:
            from deeplearning4j_tpu.observability import introspection

            # introspection state must exist BEFORE _build/device
            # placement so the stat vectors ride in upd_state (replicated
            # under _upd_layout)
            introspection.ensure_state(net)
        numerics_on = getattr(net.conf, "numerics", None) is not None
        if numerics_on:
            from deeplearning4j_tpu.observability import numerics

            # precision-ledger state likewise rides replicated
            numerics.ensure_state(net)
        if self._step is None:
            if self.update_sharding == zero_mod.ZERO:
                self._build_zero(net)
            else:
                self._build(net)
        params = jax.device_put(net.params, self._params_layout)
        upd_state = jax.device_put(net.updater_state, self._upd_layout)
        ns = jax.device_put(net.net_state, self._repl_sharding)
        K = self.mesh.shape[backend.AXIS_DATA]
        # sharding ledger under the master's actual layouts: replicated
        # params/updater read factor = mesh size — the measured baseline
        # the ZeRO update sharding (ROADMAP item 2) regresses against.
        # Metadata walk only, before the first (donating) dispatch.
        # Component matches the rest of this loop's telemetry (step_guard
        # and PhaseStats label "sync_master" for subclasses too, so the
        # ledger stays joinable with the step metrics).
        shardstats.record_ledger(
            "sync_master",
            {"params": params, "updater_state": upd_state, "net_state": ns},
            data_axis_size=K,
            notes=(self._zero_layout.notes()
                   if self._zero_layout is not None else None))
        it = iter(iterator)
        while True:
            # phases ≙ CommonSparkTrainingStats: fetch (split/repartition),
            # place (broadcast), dispatch (mapPartitions fit; the gradient
            # all-reduce — the reference's aggregate — is inside the program)
            with self._phases.phase("fetch"):
                try:
                    ds = next(it)
                except StopIteration:
                    break
            if res is not None and res.skip_batch():
                continue   # auto-resume: batch already covered by the ckpt
            if preemption_requested():
                # fold live state back so the priority checkpoint sees it
                net.params, net.updater_state, net.net_state = (
                    params, upd_state, ns)
                if res is not None:
                    res.on_preempt(net)
                break
            n_real = len(ds)
            if len(ds) % K:
                ds = ds.pad_batch(((len(ds) + K - 1) // K) * K)
            emask = None
            step0 = net.iteration   # pre-advance: barrier polls the SAME
            if self._elastic is not None:   # step begin_window decided on
                emask = self._elastic.begin_window(step0)
                if emask.min() >= 1.0:
                    emask = None    # healthy mesh: untouched fast path
            feats = ds.features
            inj = get_fault_injector()
            if inj is not None and inj.has_poison():
                # deterministic chaos: data slot k owns the contiguous
                # row block [k*B/K, (k+1)*B/K) of the global batch
                # (poison flows regardless of the guard — the unguarded
                # arm is the bench/test contrast)
                if not self._stab_workers:
                    self._stab_workers = [
                        f"d{s[0].id}" for s in self._data_slot_devices()]
                # poison_rows copies host-side only when a rule matches
                feats = inj.poison_rows(self._stab_workers, step0, feats, K)
            t0 = time.perf_counter()
            with self._phases.phase("place"):
                x = jax.device_put(jnp.asarray(feats), self._data_sharding)
                y = jax.device_put(jnp.asarray(ds.labels), self._data_sharding)
                fm = None if ds.features_mask is None else jax.device_put(
                    jnp.asarray(ds.features_mask), self._data_sharding)
                if (self._elastic is None and stab_rt is None
                        and self.update_sharding != zero_mod.ZERO):
                    lm_host = ds.labels_mask
                elif emask is not None:
                    lm_host = self._evicted_labels_mask(ds, emask, K)
                elif ds.labels_mask is not None:
                    lm_host = ds.labels_mask
                else:
                    # elasticity/stability/ZeRO keep ONE trace: the mask
                    # argument is always an array (all-ones == the
                    # unmasked mean; the ZeRO step also reads the
                    # per-shard mask sums as its loss weights), so the
                    # first eviction or poisoned row flips values, not
                    # the pytree — no recompile at the moment the mesh
                    # degrades
                    lm_host = np.ones(
                        (len(ds),) + (1,) * (ds.labels.ndim - 2),
                        np.float32)
                lm = None if lm_host is None else jax.device_put(
                    jnp.asarray(lm_host), self._data_sharding)
            with step_guard("sync_step", component="sync_master",
                            iteration=net.iteration):
                with self._phases.phase("dispatch"):
                    if res is not None:
                        out = res.step(
                            lambda: self._step(
                                params, upd_state, ns,
                                jnp.asarray(float(net.iteration)),
                                x, y, net._keys.next(), fm, lm),
                            net.iteration, net=net)
                    else:
                        out = self._step(
                            params, upd_state, ns,
                            jnp.asarray(float(net.iteration)),
                            x, y, net._keys.next(), fm, lm,
                        )
                    if stab_rt is not None:
                        params, upd_state, ns, loss, slot_poison = out
                        # device-side add only; read at check boundaries
                        stab_rt.accumulate(poison_flags=slot_poison)
                    else:
                        params, upd_state, ns, loss = out
            if introspect:
                # live device reference for listeners (the facade's
                # updater_state is stale until the loop exits); no
                # transfer until a reporting interval reads it
                net._introspect_live = upd_state[introspection.STATE_KEY]
            if numerics_on:
                from deeplearning4j_tpu.observability import numerics

                net._numerics_live = upd_state[numerics.STATE_KEY]
            net.score_value = loss  # device scalar; fetched lazily on read
            net.iteration += 1
            if stab_rt is not None:
                from deeplearning4j_tpu.resilience import stability

                action = stab_rt.poll_master(
                    step=net.iteration, losses=loss,
                    stab_state=upd_state[stability.STATE_KEY],
                    elastic=self._elastic,
                    can_rewind=res is not None and res.cm is not None)
                if action == "backoff":
                    upd_state = stability.apply_lr_backoff_tree(
                        upd_state, policy)
                elif action == "rewind":
                    net.params, net.updater_state, net.net_state = (
                        params, upd_state, ns)
                    if stab_rt.rewind(net, res.cm, mesh=self.mesh) is not None:
                        # restage the rewound facade state onto the mesh
                        params = jax.device_put(net.params,
                                                self._params_layout)
                        upd_state = jax.device_put(net.updater_state,
                                                   self._upd_layout)
                        ns = jax.device_put(net.net_state,
                                            self._repl_sharding)
            if res is not None and res.cm is not None:
                trigger = res.cm.due(net.iteration)
                if trigger is not None:
                    # fold live state into the facade only when a save is
                    # actually due (the snapshot reads net.*)
                    net.params, net.updater_state, net.net_state = (
                        params, upd_state, ns)
                    res.cm.save(net, trigger=trigger)
            if self.collect_stats:
                if self._workers is None:
                    if self._elastic is not None:
                        self._workers = (
                            self._elastic.cfg.make_worker_telemetry(
                                "sync_master"))
                    else:
                        self._workers = WorkerTelemetry("sync_master")
                    if self._elastic is not None:
                        self._elastic.attach_detector(self._workers.detector)
                with self._phases.phase("device_sync"):
                    worker_times = self._measure_worker_sync(loss, t0)
                step_s = time.perf_counter() - t0
                self._stats["step_time_ms"].append(step_s * 1e3)
                per_dev = max(1, len(ds) // K)
                inj = get_fault_injector()
                for worker, w_s in (worker_times
                                    or {str(i): step_s
                                        for i in range(K)}).items():
                    if inj is not None:
                        w_s += inj.worker_delay(worker)
                    self._workers.observe(worker, w_s, batch=per_dev)
            if self._elastic is not None:
                # synchrony-barrier simulation (fault injection only):
                # lockstep pays the slowest ACTIVE worker's delay per step
                self._elastic.window_barrier(step0)
            self._stats["steps"] += 1
            self._phases.steps += 1
            if net.listeners:
                # listeners read model.params/updater_state; the facade's
                # stale references point at buffers the jitted step
                # DONATED — rebind to the live step outputs (reference
                # assignment only, no copy; the loop-exit fold-back does
                # exactly this)
                net.params, net.updater_state, net.net_state = (
                    params, upd_state, ns)
            notify_listeners(net, n_real)
        net.params, net.updater_state, net.net_state = params, upd_state, ns
        if stab_rt is not None:
            stab_rt.flush(net)   # tail past the last check boundary

    def _measure_worker_sync(self, loss, t_step0: float) -> Dict[str, float]:
        """Device-sync on the step result, measuring each device's shard
        arrival relative to the host step start.  Blocking the shards in
        turn completes no later than the single ``block_until_ready`` it
        replaces.

        Measurement honesty: the loss is the all-reduced replicated
        scalar, and the collective gates every device on the slowest one
        — so the per-device times here share the cluster critical path
        rather than attributing blame (post-collective skew, e.g. the
        updater apply, is the visible part).  They give the registry an
        accurate per-step cluster distribution; real per-worker
        attribution arrives via ``WorkerTelemetry.observe`` from
        per-host timing in multi-process deployments (this method is the
        in-process seam)."""
        times: Dict[str, float] = {}
        try:
            shards = list(loss.addressable_shards)
        except Exception:
            shards = []
        for sh in shards:
            try:
                jax.block_until_ready(sh.data)
            except Exception:
                continue
            times[f"d{sh.device.id}"] = time.perf_counter() - t_step0
        jax.block_until_ready(loss)
        return times

    def training_stats(self):
        out = dict(self._stats)
        out["step_time_ms"] = list(out["step_time_ms"])  # JSON-safe snapshot
        out.update(self._phases.as_dict())
        if self._workers is not None:
            out["cluster"] = self._workers.cluster_view()
        if self._elastic is not None:
            out["elastic"] = self._elastic.summary()
        return out


class ParameterAveragingTrainingMaster(TrainingMaster):
    """Reference-semantics parameter averaging over the mesh.

    ``workers`` replicas each train ``averaging_frequency`` minibatches
    locally (zero communication — vmapped replicas), then parameters (and
    optionally updater state) are averaged: the reference's
    broadcast→train→aggregate cycle collapsed into one XLA program where
    "aggregate" is an ICI all-reduce instead of a driver tree-reduce.
    """

    def __init__(self, workers: Optional[int] = None, batch_size: int = 32,
                 averaging_frequency: int = 5, average_updaters: bool = True,
                 prefetch_size: int = 2, repartition: str = "always",
                 mesh: Optional[Mesh] = None, collect_stats: bool = False,
                 elastic=False, update_sharding: str = zero_mod.REPLICATED):
        self.mesh = mesh or backend.default_mesh()
        self.workers = workers or self.mesh.shape[backend.AXIS_DATA]
        self.batch_size = batch_size
        self.averaging_frequency = averaging_frequency
        self.average_updaters = average_updaters
        self.prefetch_size = prefetch_size
        self.collect_stats = collect_stats
        # forwarded to each per-fit ParallelWrapper; validated HERE so a
        # bad mode (or ZeRO with a local-SGD frequency) fails at
        # construction like the other masters, not at the first fit
        self.update_sharding = zero_mod.validate_mode(update_sharding,
                                                      self.mesh)
        if (self.update_sharding == zero_mod.ZERO
                and self.averaging_frequency != 1):
            raise ValueError(
                "update_sharding='zero' requires averaging_frequency=1 "
                f"(got {self.averaging_frequency}): local-SGD windows "
                "need full per-replica updater state between averages")
        # One persistent controller shared by every per-fit ParallelWrapper:
        # eviction state and flag budgets survive epoch boundaries instead
        # of resetting with each epoch's fresh wrapper.
        self._elastic: Optional[ElasticController] = None
        if elastic is not False and elastic is not None:
            ecfg = (elastic if isinstance(elastic, ElasticConfig)
                    else ElasticConfig())
            self._elastic = ElasticController(
                "parallel_wrapper", [str(k) for k in range(self.workers)],
                config=ecfg)
        self._stats: Dict[str, Any] = {"windows": 0}
        self._phases = PhaseStats(component="param_avg_master")

    @property
    def elastic(self) -> Optional[ElasticController]:
        """The elasticity state machine (None unless ``elastic=`` was
        passed) — ``elastic.summary()`` is the operator view."""
        return self._elastic

    def execute_training(self, net, iterator):
        from deeplearning4j_tpu.parallel.data_parallel import ParallelWrapper

        pw = ParallelWrapper(
            net,
            workers=self.workers,
            prefetch_size=self.prefetch_size,
            averaging_frequency=self.averaging_frequency,
            average_updaters=self.average_updaters,
            mesh=self.mesh,
            elastic=self._elastic if self._elastic is not None else False,
            update_sharding=self.update_sharding,
        )
        with self._phases.phase("fit"):
            pw.fit(iterator)
        self._stats["windows"] += 1
        self._phases.steps += pw.iteration  # accumulate across epochs

    def training_stats(self):
        out = dict(self._stats)
        out.update(self._phases.as_dict())
        if self._elastic is not None:
            out["elastic"] = self._elastic.summary()
        return out


class DistributedNetwork:
    """Facade pairing a network with a TrainingMaster (reference
    ``SparkDl4jMultiLayer.java:72``: wraps net + master, fit(RDD)).
    Evaluation shards the eval batch over the mesh the same way."""

    def __init__(self, net, training_master: TrainingMaster):
        self.net = net
        self.master = training_master

    def fit(self, iterator, epochs: int = 1):
        try:
            for _ in range(epochs):
                self.master.execute_training(self.net, iterator)
        except Exception as e:
            # leave the same diagnosis artifact a hang would (flight
            # events + live spans + registry), then re-raise
            crash_dump("fit_exception",
                       master=type(self.master).__name__, error=repr(e))
            raise
        return self.net

    def evaluate(self, iterator, evaluation=None):
        """Evaluation with the forward pass sharded over the master's mesh
        (≙ Spark evaluation as mapPartitions + tree-aggregated counts: each
        device scores its batch shard, metrics accumulate on host)."""
        from deeplearning4j_tpu.evaluation import Evaluation

        ev = evaluation or Evaluation()
        mesh = getattr(self.master, "mesh", None)
        out_fn = self.net.output
        pad_to = 1
        # sharded fast path needs the net's cached jittable forward
        # (MultiLayerNetwork); ComputationGraph falls back to net.output
        if (mesh is not None and backend.AXIS_DATA in mesh.shape
                and hasattr(self.net, "_output_fn")):
            pad_to = mesh.shape[backend.AXIS_DATA]
            if getattr(self, "_eval_mesh", None) is not mesh:
                data = NamedSharding(mesh, P(backend.AXIS_DATA))
                # params/net-state shardings are taken from the ARGS
                # (None = as-given): after a ZeRO fit the facade holds
                # genuinely sharded params, and pinning them replicated
                # here would reject them — GSPMD gathers what the
                # forward needs either way
                self._eval_fn = jax.jit(
                    auto_partitioned(mesh)(self.net._output_fn()),
                    in_shardings=(None, None, data, data))
                self._eval_mesh = mesh
            sharded = self._eval_fn

            def out_fn(x, fmask=None):  # noqa: E306
                return sharded(self.net.params, self.net.net_state,
                               jnp.asarray(x),
                               None if fmask is None else jnp.asarray(fmask))

        for ds in iterator:
            n = len(ds)
            if n % pad_to:
                ds_run = ds.pad_batch(((n + pad_to - 1) // pad_to) * pad_to)
            else:
                ds_run = ds
            out = np.asarray(out_fn(ds_run.features,
                                    fmask=ds_run.features_mask))[:n]
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    def score(self, dataset):
        return self.net.score(dataset.features, dataset.labels)

    def training_stats(self):
        return self.master.training_stats()
