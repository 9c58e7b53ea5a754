"""Single-host data parallelism — the ParallelWrapper redesign.

Reference: ``deeplearning4j-core/.../parallelism/ParallelWrapper.java:37-205``:
N Java threads each own a model replica pinned to a device; a round-robin
queue feeds them; every ``averagingFrequency`` iterations params are averaged
via ``Nd4j.averageAndPropagate`` (and optionally updater state too).

TPU-native redesign: no threads, no queues, no host-side averaging.  The K
replicas are ONE jitted program over a ``Mesh``:

- replica params are a stacked pytree (leading axis K) sharded over the
  'data' mesh axis — each device holds exactly its replica;
- the per-replica train step is ``jax.vmap`` of the single-model step, so
  the whole "N workers train independently" phase is a single XLA program
  with zero communication;
- parameter averaging is ``mean over the replica axis`` — XLA lowers it to
  an all-reduce that rides ICI (replacing averageAndPropagate), followed by
  re-broadcast.  Updater-state averaging is the same tree-map, gated by
  ``average_updaters`` exactly like the reference.

``averaging_frequency=1`` + SGD reproduces synchronous DP; higher
frequencies reproduce the reference's looser local-SGD semantics bit-for-bit
(see tests/test_parallel.py equivalence tests).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.backend import device as backend
from deeplearning4j_tpu.observability import (
    PhaseTimers, WorkerTelemetry, get_registry, instrument, step_guard,
)
from deeplearning4j_tpu.observability import shardstats
from deeplearning4j_tpu.optimize import updaters as upd
from deeplearning4j_tpu.parallel import zero as zero_mod
from deeplearning4j_tpu.parallel.elastic import ElasticConfig, ElasticController


def _stack_tree(tree, k: int):
    return jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a[None], (k,) + a.shape), tree)


_SENTINEL = object()


class _WindowAssembler:
    """Background window assembly: a producer thread groups minibatches into
    stacked [F, K, B, ...] host arrays so padding/stacking overlaps device
    execution of the previous window (the native-ETL principle applied to
    the DP hot path; producer errors re-raise on the consumer side; an
    abandoned consumer unblocks the producer via the stop event)."""

    def __init__(self, iterator, K: int, F: int, stack_fn, prefetch: int = 2):
        self._queue: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch))
        self._error: Optional[BaseException] = None
        self._stop = threading.Event()

        def put(item) -> bool:
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        def run():
            try:
                window = []
                for ds in iterator:
                    window.append(ds)
                    if len(window) == K * F:
                        if not put(stack_fn(window, K * F)):
                            return
                        window = []
                if window and not self._stop.is_set():
                    # tail handling: emit the full frames as their own
                    # window first — a whole-tail per-replica weight would
                    # also discard those replicas' REAL earlier minibatches
                    n_full = (len(window) // K) * K
                    if n_full and not put(stack_fn(window[:n_full], n_full)):
                        return
                    window = window[n_full:]
                if window and not self._stop.is_set():
                    # partial final frame: duplicate the tail minibatch to
                    # fill the K replica slots (keeps a compiled [1, K, ...]
                    # shape); n_real lets the stacker weight the pad-filled
                    # replicas out of the average (they'd double-count the
                    # duplicate)
                    n_real = len(window)
                    while len(window) % K:
                        window.append(window[-1])
                    put(stack_fn(window, n_real))
            except BaseException as e:
                self._error = e
            finally:
                put(_SENTINEL)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def close(self):
        self._stop.set()
        try:  # unblock a producer waiting on a full queue
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        if self._thread is not None:
            # bounded join: the drain above freed the queue, so the
            # producer reaches its sentinel promptly — and a re-iteration
            # never races a half-dead assembler on the same queue
            self._thread.join(timeout=5.0)
            if not self._thread.is_alive():
                self._thread = None

    def __iter__(self):
        try:
            while True:
                item = self._queue.get()
                if item is _SENTINEL:
                    if self._error is not None:
                        err, self._error = self._error, None
                        raise RuntimeError("window assembly failed") from err
                    return
                yield item
        finally:
            self.close()


class ParallelWrapper:
    """Data-parallel trainer over the local mesh.

    Usage mirrors the reference builder:
        pw = ParallelWrapper(net, workers=8, prefetch_size=2,
                             averaging_frequency=3, average_updaters=True)
        pw.fit(iterator)
    """

    def __init__(
        self,
        net,
        workers: Optional[int] = None,
        prefetch_size: int = 2,
        averaging_frequency: int = 1,
        average_updaters: bool = True,
        mesh: Optional[Mesh] = None,
        collect_worker_stats: bool = False,
        checkpoint_manager=None,
        retry_policy=None,
        elastic=False,
        update_sharding: str = zero_mod.REPLICATED,
    ):
        self.net = net
        # resilience wiring (docs/resilience.md): auto-resume on fit entry,
        # window-boundary saves, clean preemption stop, transient retry
        self.checkpoint_manager = checkpoint_manager
        self.retry_policy = retry_policy
        self.mesh = mesh or backend.default_mesh()
        self.workers = workers or self.mesh.shape[backend.AXIS_DATA]
        if self.workers != self.mesh.shape[backend.AXIS_DATA]:
            raise ValueError(
                f"workers={self.workers} must equal the mesh data-axis size "
                f"{self.mesh.shape[backend.AXIS_DATA]}"
            )
        self.prefetch_size = prefetch_size
        self.averaging_frequency = max(1, averaging_frequency)
        self.average_updaters = average_updaters
        self._step_fn = None
        self.iteration = 0
        # wait≙time blocked on window assembly (host ETL), dispatch≙the
        # vmapped train window + averaging all-reduce
        self._phases = PhaseTimers("parallel_wrapper")
        # per-replica step time + throughput -> labeled registry families
        # + straggler detection (SparkNet/DeepSpark: the run goes at the
        # slowest replica's speed).  OPT-IN because the measurement costs
        # one device sync per window, which breaks the default loop's
        # async overlap of host window-assembly with device execution
        # (same gating as SyncTrainingMaster's collect_stats).
        self.collect_worker_stats = collect_worker_stats
        self._workers: Optional[WorkerTelemetry] = None
        # elasticity (docs/resilience.md "Elasticity"): evict a straggling,
        # hung, or dead replica from the averaging collective via a runtime
        # [K] weight mask (no recompile), renormalize over the healthy set,
        # re-admit at a window boundary after the fault clears.  Pass True
        # or an ElasticConfig; requires worker stats for straggler verdicts.
        # An existing ElasticController is adopted as-is so eviction state
        # can outlive one wrapper (ParameterAveragingTrainingMaster builds
        # a fresh wrapper per epoch around one persistent controller).
        self._elastic: Optional[ElasticController] = None
        self._ones_w: Optional[np.ndarray] = None
        self._stab_rt = None   # StabilityRuntime (net.conf.stability)
        # ZeRO update sharding (arXiv 2004.13336, docs/PARALLELISM.md
        # "ZeRO"): persistent params + updater state live sharded 1/K
        # per device; each window all-gathers the params, computes
        # per-replica gradients, moves every replica's gradient shard to
        # its owner (an all-to-all — the wrapper's averaging semantics
        # need each replica's OWN gradient because the per-replica Adam
        # updates it averages are nonlinear in them; same wire bytes as
        # a reduce-scatter), and applies the weighted-average update to
        # the local shard.  Restricted to averaging_frequency=1 +
        # average_updaters=True: higher frequencies are local SGD, where
        # every replica needs its own full moments between averages —
        # there is nothing shardable.
        self.update_sharding = zero_mod.validate_mode(update_sharding,
                                                      self.mesh)
        self._zero_layout: Optional[zero_mod.ZeroLayout] = None
        if self.update_sharding == zero_mod.ZERO:
            if self.averaging_frequency != 1:
                raise ValueError(
                    "update_sharding='zero' requires averaging_frequency"
                    f"=1 (got {self.averaging_frequency}): local-SGD "
                    "windows need full per-replica updater state between "
                    "averages")
            if not self.average_updaters:
                raise ValueError(
                    "update_sharding='zero' requires average_updaters="
                    "True: un-averaged updater state is per-replica and "
                    "cannot be sharded")
            self._zero_layout = zero_mod.ZeroLayout(self.mesh, self.workers)
        if isinstance(elastic, ElasticController):
            if elastic.K != self.workers:
                raise ValueError(
                    f"elastic controller tracks {elastic.K} workers, "
                    f"wrapper has {self.workers}")
            self.collect_worker_stats = True
            self._elastic = elastic
        elif elastic is not False and elastic is not None:
            cfg = elastic if isinstance(elastic, ElasticConfig) else ElasticConfig()
            self.collect_worker_stats = True
            self._elastic = ElasticController(
                "parallel_wrapper", [str(k) for k in range(self.workers)],
                config=cfg)

    @property
    def elastic(self) -> Optional[ElasticController]:
        """The elasticity state machine (None unless ``elastic=`` was
        passed) — ``elastic.summary()`` is the operator view."""
        return self._elastic

    # -- sharding specs ----------------------------------------------------
    def _replica_sharding(self):
        """Leading replica axis sharded over 'data'; inner dims replicated."""
        return NamedSharding(self.mesh, P(backend.AXIS_DATA))

    def _build(self):
        if self.update_sharding == zero_mod.ZERO:
            return self._build_zero()
        from deeplearning4j_tpu.observability import introspection, numerics

        net = self.net
        cfg = net.conf.updater
        policy = net.conf.stability
        plan = introspection.plan_for(net)
        nplan = numerics.plan_for(net)
        lr_overrides = {
            l.name: l.learning_rate for l in net.layers if l.learning_rate is not None
        }
        avg_freq = self.averaging_frequency
        average_updaters = self.average_updaters

        def one_replica_step(params, upd_state, net_state, iteration, x, y, rng, fm, lm):
            nstate = None
            if nplan is not None:
                nstate, upd_state = numerics.split_state(upd_state)
            if plan is not None:
                _, upd_state = introspection.split_state(upd_state)
            # iteration is unmapped under the vmap, so this predicate
            # stays a true lax.cond per replica (not a select)
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
                new_params = dict(params)
                for lname, u in updates.items():
                    new_params[lname] = upd.apply_updates(params[lname], u)
                # vmapped: each replica refreshes its own [L] slice, so
                # the window exits with a [K, L] per-replica view
                introspection.attach(
                    new_us, plan, grads=grads, params=params,
                    new_params=new_params, iteration=iteration,
                    act_stats=act_stats)
                numerics.attach(
                    new_us, nplan, grads=grads, iteration=iteration,
                    act_stats=act_stats, prev=nstate, now=now)
                return new_params, new_us, new_ns, loss, jnp.ones(())
            # non-finite step guard per replica (resilience/stability.py):
            # a poisoned replica's step is a device-side no-op; the window
            # averaging below ALSO weights it out of the collective
            from deeplearning4j_tpu.resilience import stability

            stab, inner = stability.split_state(upd_state)
            (_, (loss, aux)), grads = jax.value_and_grad(
                stability.scaled_loss(net._loss_fn, stab), has_aux=True)(
                params, net_state, x, y, rng, fm, lm, None, **kw)
            new_ns, _, act_stats = numerics.unpack_aux(plan, nplan, aux)
            new_params, new_us, new_ns, finite = (
                stability.apply_guarded_update(
                    policy, cfg, stab, inner, params, net_state,
                    loss, grads, new_ns, iteration, lr_overrides))
            introspection.attach(
                new_us, plan, grads=grads, params=params,
                new_params=new_params, iteration=iteration,
                act_stats=act_stats, grad_scale=1.0 / stab["loss_scale"])
            numerics.attach(
                new_us, nplan, grads=grads, iteration=iteration,
                act_stats=act_stats, grad_scale=1.0 / stab["loss_scale"],
                prev=nstate, now=now)
            return new_params, new_us, new_ns, loss, finite.astype(jnp.float32)

        vstep = jax.vmap(one_replica_step, in_axes=(0, 0, 0, None, 0, 0, 0, 0, 0))

        def fit_window(params_k, upd_k, ns_k, iteration, xs, ys, rngs, fms, lms,
                       weights):
            """avg_freq minibatches per replica, then average.
            xs: [avg_freq, K, B, ...]; weights: [K] replica weights — 0 for
            evicted replicas (degraded mode) and pad-filled tail replicas,
            1 otherwise.  The average is renormalized over the weighted
            set and broadcast into ALL K slots, so an evicted replica's
            slot always holds the current healthy average (that broadcast
            IS the re-admission catch-up).  With the stability engine on,
            a replica with ANY non-finite step this window is additionally
            weighted out (poison masking — same zero-recompile mask), and
            the window reports [K] poison flags + a non-finite step count."""

            def body(carry, inp):
                p, u, n, it = carry
                x, y, rng, fm, lm = inp
                p, u, n, loss, fin = vstep(p, u, n, it, x, y, rng, fm, lm)
                return (p, u, n, it + 1.0), (loss, fin)

            (params_k, upd_k, ns_k, _), (losses, finites) = jax.lax.scan(
                body, (params_k, upd_k, ns_k, iteration), (xs, ys, rngs, fms, lms)
            )
            if policy is not None:
                # [K] 1 where every step of the window was finite
                win_finite = jnp.min(finites, axis=0)
                w_eff = weights * win_finite
                # all real replicas poisoned: fall back to the original
                # weights — every per-replica update was already skipped
                # device-side, so the average stays finite either way
                safe = jnp.sum(w_eff) > 0
                weights = jnp.where(safe, w_eff, weights)
            # parameter averaging: weighted all-reduce over the replica
            # axis then re-broadcast (reference averageAndPropagate
            # semantics, renormalized over the healthy/unpadded set —
            # sum(w)=K with all weights 1 reproduces the plain mean
            # bit-for-bit ... the caller guarantees sum(w) > 0)
            wsum = jnp.sum(weights)

            def wavg(a):
                w = weights.reshape((a.shape[0],) + (1,) * (a.ndim - 1))
                m = jnp.sum(a * w, 0, keepdims=True) / wsum
                return jnp.broadcast_to(m.astype(a.dtype), a.shape)

            params_k = jax.tree_util.tree_map(wavg, params_k)
            ns_k = jax.tree_util.tree_map(wavg, ns_k)
            if average_updaters:
                # the introspection and numerics subtrees are PER-REPLICA
                # views — averaging them would erase exactly the
                # per-replica divergence signal they exist to expose
                held = {k: upd_k[k]
                        for k in (introspection.STATE_KEY, numerics.STATE_KEY)
                        if k in upd_k}
                if held:
                    rest = {k: v for k, v in upd_k.items() if k not in held}
                    rest = jax.tree_util.tree_map(wavg, rest)
                    rest.update(held)
                    upd_k = rest
                else:
                    upd_k = jax.tree_util.tree_map(wavg, upd_k)
            if policy is not None:
                return (params_k, upd_k, ns_k, losses,
                        1.0 - win_finite, jnp.sum(1.0 - finites))
            return params_k, upd_k, ns_k, losses

        self._step_fn = instrument(
            jax.jit(fit_window, donate_argnums=(0, 1, 2)),
            "ParallelWrapper.fit_window", argnums=(3, 4, 5, 6, 7, 8, 9))

    def _build_zero(self):
        """The ZeRO-sharded window (update_sharding="zero",
        averaging_frequency=1): persistent params + optimizer moments
        live sharded 1/K per device.  Inside a ``shard_map`` each device
        all-gathers the params, runs ITS replica's forward/backward
        (same per-replica RNG keys and per-layer gradient normalization
        as the vmapped replicated window), and an all-to-all hands every
        replica's gradient shard to its owner.  Outside, under GSPMD,
        the per-replica elementwise updates are computed against the
        SHARED sharded moments, weighted-averaged over replicas (the
        elastic / pad / poison ``[K]`` weight mask applies unchanged),
        and applied to the local shard — reproducing the replicated
        window's average-of-per-replica-updates semantics exactly.  The
        ``__stability__`` / ``__introspect__`` subtrees stay stacked per
        replica as in replicated mode (recorded in the ledger notes).
        The ``__numerics__`` precision-ledger subtree is carried through
        UNCHANGED (stale) — ZeRO's sharded update has no per-replica
        gradient view to measure; harvest reports whatever the last
        non-ZeRO refresh wrote (docs/observability.md "Numerics")."""
        from deeplearning4j_tpu.observability import introspection, numerics
        from deeplearning4j_tpu.resilience import stability

        net = self.net
        cfg = net.conf.updater
        cfg_sharded = zero_mod.no_norm(cfg)
        policy = net.conf.stability
        plan = introspection.plan_for(net)
        lr_overrides = {
            l.name: l.learning_rate for l in net.layers
            if l.learning_rate is not None
        }
        K = self.workers
        mesh = self.mesh
        layout = self._zero_layout
        pmask = layout.mask(net.params)
        p_specs = layout.tree_specs(net.params)
        kw = ({"collect_acts": True}
              if plan is not None and plan.collect_acts else {})
        AX = zero_mod.AXIS

        def fit_window(p_sh, upd_k, ns_k, iteration, xs, ys, rngs, fms, lms,
                       weights):
            num_k, upd_k = numerics.split_state(upd_k)
            _, upd2 = introspection.split_state(upd_k)
            if policy is not None:
                stab_k, inner_sh = stability.split_state(upd2)
            else:
                stab_k, inner_sh = None, upd2
            # F == 1 enforced at construction: one frame per window
            x1, y1, rng1 = xs[0], ys[0], rngs[0]
            fm1 = None if fms is None else fms[0]
            lm1 = None if lms is None else lms[0]
            has_fm, has_lm = fm1 is not None, lm1 is not None

            def local(p_blk, ns_blk, xk, yk, rngk, *rest):
                i = 0
                fmk = rest[i][0] if has_fm else None
                i += 1 if has_fm else 0
                lmk = rest[i][0] if has_lm else None
                i += 1 if has_lm else 0
                scale = (jax.tree_util.tree_map(lambda a: a[0], rest[i])
                         ["loss_scale"] if policy is not None else None)
                p_full = zero_mod.all_gather_tree(p_blk, pmask)
                ns_local = jax.tree_util.tree_map(lambda a: a[0], ns_blk)
                xk0, yk0, rngk0 = xk[0], yk[0], rngk[0]

                def lf(p, n):
                    loss, aux = net._loss_fn(p, n, xk0, yk0, rngk0, fmk,
                                             lmk, None, **kw)
                    if policy is not None:
                        return loss * scale, (loss, aux)
                    return loss, (loss, aux)

                (_, (loss, aux)), g = jax.value_and_grad(
                    lf, has_aux=True)(p_full, ns_local)
                new_ns, _, act_stats = introspection.unpack_aux(plan, aux)
                if policy is not None:
                    inv = 1.0 / scale
                    g = jax.tree_util.tree_map(lambda a: a * inv, g)
                    finite = stability.all_finite(loss, g)
                else:
                    finite = jnp.ones((), jnp.bool_)
                outs = []
                if plan is not None:
                    # per-replica per-layer grad norms, measured like
                    # replicated mode: raw (unnormalized) unscaled grads
                    outs.append(zero_mod.tree_norms(plan, g)[None])
                # per-replica per-layer normalization on the FULL
                # gradient (exact replicated semantics), BEFORE the
                # scatter — the sharded updater runs with norm off
                g = upd.normalize_tree(cfg, g)
                g_all = zero_mod.all_to_all_tree(g, K)
                head = [g_all, loss[None], finite[None],
                        jax.tree_util.tree_map(lambda a: a[None], new_ns)]
                if act_stats is not None:
                    outs.append(jax.tree_util.tree_map(
                        lambda a: a[None], act_stats))
                return tuple(head + outs)

            in_specs = [p_specs, P(AX), P(AX), P(AX), P(AX)]
            args = [p_sh, ns_k, x1, y1, rng1]
            if has_fm:
                in_specs.append(P(AX)); args.append(fm1)
            if has_lm:
                in_specs.append(P(AX)); args.append(lm1)
            if policy is not None:
                in_specs.append(P(AX)); args.append(stab_k)
            out_specs = [zero_mod.grad_stack_specs(net.params, K),
                         P(AX), P(AX), P(AX)]
            if plan is not None:
                out_specs.append(P(AX))
            if kw:
                out_specs.append(P(AX))
            out = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                            out_specs=tuple(out_specs),
                            check_vma=False)(*args)
            g_all, losses_k, fin_k, new_ns_k = out[0], out[1], out[2], out[3]
            idx = 4
            gn_k = act_k = None
            if plan is not None:
                gn_k = out[idx]; idx += 1
            if kw:
                act_k = out[idx]
            g_all = {ln: lg for ln, lg in g_all.items() if lg}
            fin_f = fin_k.astype(jnp.float32)
            weights_eff = weights
            if policy is not None:
                # poison masking: a replica with a non-finite step is
                # weighted out; all real replicas poisoned falls back to
                # the original weights (each update is zeroed anyway)
                w_eff = weights * fin_f
                safe = jnp.sum(w_eff) > 0
                weights_eff = jnp.where(safe, w_eff, weights)
            wsum = jnp.sum(weights_eff)

            def rk(vec, a):
                return vec.reshape((a.shape[0],) + (1,) * (a.ndim - 1))

            def wavg_k(a):          # [K, ...] -> [...] weighted mean
                return jnp.sum(a * rk(weights_eff, a), 0) / wsum

            def wavg_bcast(a):      # [K, ...] -> all K slots = the mean
                m = jnp.sum(a * rk(weights_eff, a), 0,
                            keepdims=True) / wsum
                return jnp.broadcast_to(m.astype(a.dtype), a.shape)

            # per-replica elementwise updates against the SHARED sharded
            # moments — the all-to-all delivered g_all leaves as
            # [K(replica), shard...], so this is shard-local work
            def per_k(gk):
                return upd.update(cfg_sharded, gk, inner_sh, iteration,
                                  lr_overrides, params=p_sh)

            updates_k, new_inner_k = jax.vmap(per_k)(g_all)
            if policy is not None:
                lr_scale_k = stab_k["lr_scale"]
                if policy.skip_nonfinite:
                    sc_k = jnp.where(fin_f > 0, lr_scale_k, 0.0)
                    updates_k = jax.tree_util.tree_map(
                        lambda u: jnp.where(rk(fin_f, u) > 0, u,
                                            jnp.zeros_like(u))
                        * rk(sc_k, u), updates_k)
                    new_inner_k = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(rk(fin_f, n) > 0, n,
                                               o[None].astype(n.dtype)),
                        new_inner_k, inner_sh)
                    new_ns_k = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(rk(fin_f, n) > 0, n, o),
                        new_ns_k, ns_k)
                else:
                    updates_k = jax.tree_util.tree_map(
                        lambda u: u * rk(lr_scale_k, u), updates_k)
            u_mean = jax.tree_util.tree_map(wavg_k, updates_k)
            new_p = dict(p_sh)
            for ln, u in u_mean.items():
                new_p[ln] = upd.apply_updates(p_sh[ln], u)
            new_upd: Dict[str, Any] = jax.tree_util.tree_map(wavg_k,
                                                             new_inner_k)
            ns_out = jax.tree_util.tree_map(wavg_bcast, new_ns_k)
            if policy is not None:
                new_stab_k = jax.vmap(
                    lambda s, f: stability.next_state(policy, s, f))(
                    stab_k, fin_k)
                new_upd[stability.STATE_KEY] = jax.tree_util.tree_map(
                    wavg_bcast, new_stab_k)
            if plan is not None:
                un = zero_mod.update_delta_norms(plan, p_sh, new_p)
                pn = zero_mod.tree_norms(plan, p_sh)
                new_upd[introspection.STATE_KEY] = \
                    zero_mod.pack_introspection(plan, iteration, gn_k, un,
                                                pn, act_k)
            if num_k is not None:
                # stale carry-through (see the docstring): structurally
                # intact so checkpoints and later non-ZeRO fits resume it
                new_upd[numerics.STATE_KEY] = num_k
            losses = losses_k[None]
            if policy is not None:
                return (new_p, new_upd, ns_out, losses, 1.0 - fin_f,
                        jnp.sum(1.0 - fin_f))
            return new_p, new_upd, ns_out, losses

        self._step_fn = instrument(
            jax.jit(fit_window, donate_argnums=(0, 1, 2)),
            "ParallelWrapper.fit_window_zero", argnums=(3, 4, 5, 6, 7, 8, 9))

    # -- fit ---------------------------------------------------------------
    def fit(self, iterator):
        """Train over an iterator of DataSets.  Each averaging window
        consumes ``workers * averaging_frequency`` minibatches (reference
        split sizing ``ParameterAveragingTrainingMaster.java:315-321``).

        Window assembly never runs on the dispatch thread: in-memory
        unmasked data goes through the native C++ slab pipeline
        (``native.Batcher`` producing whole [F*K*B] windows in one gather);
        everything else is stacked by the ``_WindowAssembler`` prefetch
        thread."""
        from deeplearning4j_tpu.datasets.iterator import (
            AsyncDataSetIterator, DataSetIterator, ListDataSetIterator,
        )
        from deeplearning4j_tpu.resilience import (
            FitResilience, get_fault_injector, preemption_requested,
        )

        if self._step_fn is None:
            self._build()

        net = self.net
        res = None
        if self.checkpoint_manager is not None or self.retry_policy is not None:
            # resume BEFORE replica stacking so the restored params are
            # what gets broadcast to the K replicas
            res = FitResilience("parallel_wrapper", self.checkpoint_manager,
                                self.retry_policy, net=net, mesh=self.mesh)
        K, F = self.workers, self.averaging_frequency
        policy = net.conf.stability
        if policy is not None:
            from deeplearning4j_tpu.resilience import stability

            # stability state must exist BEFORE replica stacking so the
            # per-replica guard/scale scalars ride in upd_k
            stability.ensure_state(net)
            if self._stab_rt is None:
                self._stab_rt = stability.StabilityRuntime(
                    "parallel_wrapper", policy,
                    worker_ids=[str(k) for k in range(K)])
        stab_rt = self._stab_rt
        introspect = getattr(net.conf, "introspection", None) is not None
        if introspect:
            from deeplearning4j_tpu.observability import introspection

            # introspection state must exist BEFORE replica stacking so
            # the per-layer stat vectors ride in upd_k as [K, L]
            introspection.ensure_state(net)
        numerics_on = getattr(net.conf, "numerics", None) is not None
        if numerics_on:
            from deeplearning4j_tpu.observability import numerics

            # precision-ledger state rides in upd_k as [K, N] likewise
            numerics.ensure_state(net)
        shard = self._replica_sharding()
        params_k, upd_k, ns_k = self._stage(net, K, shard)
        # sharding ledger over the staged trees, measured against the
        # facade's single-model trees: full replication reads K on the
        # stacked replica view; with update_sharding="zero" the params
        # and updater rows read ~1 (only the tiny stacked reserved
        # subtrees stay per replica — recorded in the notes).  Metadata
        # walk only; recorded once per fit, before the first (donating)
        # dispatch.
        shardstats.record_ledger(
            "parallel_wrapper",
            {"params": params_k, "updater_state": upd_k, "net_state": ns_k},
            logical_trees={"params": net.params,
                           "updater_state": net.updater_state,
                           "net_state": net.net_state},
            data_axis_size=K,
            notes=(self._zero_layout.notes()
                   if self._zero_layout is not None else None))

        if (isinstance(iterator, ListDataSetIterator)
                and iterator._data.features_mask is None
                and iterator._data.labels_mask is None):
            windows = self._native_windows(iterator)
        else:
            if isinstance(iterator, DataSetIterator) and iterator.async_supported():
                iterator = AsyncDataSetIterator(iterator, self.prefetch_size)
            windows = _WindowAssembler(iterator, K, F, self._stack_window,
                                       prefetch=self.prefetch_size)

        get_registry().gauge(
            "dl4j_parallel_replicas",
            "Data-parallel replica count of the active ParallelWrapper",
        ).set(K)
        if self.collect_worker_stats and self._workers is None:
            if self._elastic is not None:
                self._workers = self._elastic.cfg.make_worker_telemetry(
                    "parallel_wrapper")
            else:
                self._workers = WorkerTelemetry("parallel_wrapper")
        if self._elastic is not None and self._workers is not None:
            self._elastic.attach_detector(self._workers.detector)
        it0 = it = net.iteration
        last_losses = None
        win_iter = iter(windows)
        while True:
            t_wait0 = time.perf_counter()
            with self._phases.phase("wait_window"):
                win = next(win_iter, None)
            wait_s = time.perf_counter() - t_wait0
            if win is None:
                break
            xs, ys, fms, lms, n_batches, pad_w = win
            adv = n_batches // K
            if res is not None and res.skip_window(adv):
                # auto-resume: consume the window the restored iteration
                # already covers (it stays put — restore set it past these)
                continue
            if preemption_requested():
                self._fold_back(net, params_k, upd_k, ns_k, it, last_losses)
                if res is not None:
                    res.on_preempt(net)
                if hasattr(windows, "close"):
                    windows.close()
                self.iteration = it - it0
                return net
            weights = self._window_weights(it, pad_w)
            inj = get_fault_injector()
            if inj is not None and inj.has_poison():
                # deterministic chaos: replica k's slot is xs[:, k]
                xs = inj.poison_replica_slots(
                    [str(k) for k in range(K)], it, xs)
            t_disp0 = time.perf_counter()
            with step_guard("parallel_window",
                            component="parallel_wrapper", iteration=it):
                with self._phases.phase("dispatch"):

                    def dispatch(params_k=params_k, upd_k=upd_k, ns_k=ns_k,
                                 weights=weights):
                        rngs = jax.random.split(
                            self.net._keys.next(),
                            xs.shape[0] * K).reshape(xs.shape[0], K)
                        return self._step_fn(
                            params_k, upd_k, ns_k, jnp.asarray(float(it)),
                            jnp.asarray(xs), jnp.asarray(ys), rngs, fms, lms,
                            jnp.asarray(weights))

                    if res is not None:
                        out = res.step(dispatch, it, net=net)
                    else:
                        out = dispatch()
                    if stab_rt is not None:
                        (params_k, upd_k, ns_k, last_losses,
                         poison_k, nf_ct) = out
                        # device-side adds only; read at check boundaries
                        stab_rt.accumulate(nf_ct, poison_k)
                    else:
                        params_k, upd_k, ns_k, last_losses = out
                if self.collect_worker_stats:
                    self._publish_worker_stats(
                        last_losses, time.perf_counter() - t_disp0,
                        wait_s, xs)
            if self._elastic is not None:
                # synchrony-barrier simulation (outside the telemetry
                # window so per-worker attribution stays per-worker):
                # lockstep pays the slowest ACTIVE worker's injected
                # delay; degraded mode's win is the stall it stops paying
                self._elastic.window_barrier(it)
            it += adv
            if stab_rt is not None:
                from deeplearning4j_tpu.resilience import stability

                action = stab_rt.poll_master(
                    step=it, losses=last_losses, elastic=self._elastic,
                    # stacked [K] scale state: feeds the loss-scale /
                    # lr-scale gauges at check boundaries (nonfinite
                    # totals still come from the window accumulator)
                    stab_state=upd_k.get(stability.STATE_KEY),
                    can_rewind=res is not None and res.cm is not None)
                if action == "backoff":
                    upd_k = stability.apply_lr_backoff_tree(upd_k, policy)
                elif action == "rewind":
                    self._fold_back(net, params_k, upd_k, ns_k, it,
                                    last_losses)
                    if stab_rt.rewind(net, res.cm) is not None:
                        # restage the rewound facade state onto the mesh
                        it = net.iteration
                        params_k, upd_k, ns_k = self._stage(net, K, shard)
            if introspect:
                from deeplearning4j_tpu.observability import introspection

                # stacked [K, L] per-replica view for harvesters — a
                # device reference only, no transfer until a listener's
                # reporting interval actually reads it
                net._introspect_live = upd_k.get(introspection.STATE_KEY)
            if numerics_on:
                from deeplearning4j_tpu.observability import numerics

                # stacked [K, N] per-replica precision-ledger view
                net._numerics_live = upd_k.get(numerics.STATE_KEY)
            if net.listeners:
                # fire the facade's listeners once per averaging window
                # (reference ParallelWrapper notifies per iteration) with
                # the averaged state folded back — device-side slices,
                # no host sync unless a listener reads a value
                from deeplearning4j_tpu.models.common import notify_listeners

                self._fold_back(net, params_k, upd_k, ns_k, it, last_losses)
                # sample count excludes pad-filled tail slots (each zero
                # in pad_w is one duplicated/zero-filled minibatch slot)
                # so listener throughput reflects REAL examples; pad_w is
                # a host-built numpy [K] vector (_pad_weights), no sync
                real_slots = n_batches - (
                    0 if pad_w is None else int((pad_w == 0.0).sum()))
                notify_listeners(
                    net, real_slots
                    * (int(xs.shape[2]) if xs.ndim >= 3 else 1))
            self._phases.steps += 1
            if res is not None and res.cm is not None:
                trigger = res.cm.due(it)
                if trigger is not None:
                    # fold the averaged replica-0 state into the facade
                    # only when a save is actually due
                    self._fold_back(net, params_k, upd_k, ns_k, it,
                                    last_losses)
                    res.cm.save(net, trigger=trigger)

        self._fold_back(net, params_k, upd_k, ns_k, it, last_losses)
        if stab_rt is not None:
            stab_rt.flush(net)   # tail past the last check boundary
        self.iteration = it - it0
        return net

    def _window_weights(self, it: int, pad_w):
        """Combine the elastic eviction mask with the tail-padding weights
        into the [K] weight vector the jitted window consumes.  The
        all-ones fast path covers every healthy full window.  When every
        replica holding real data is also evicted (pathological overlap of
        a ragged tail with a degraded mesh), the eviction mask alone wins
        — training on a duplicate minibatch beats dividing by zero or
        averaging in a dead replica."""
        mask = None
        if self._elastic is not None:
            mask = self._elastic.begin_window(it)
            if mask.min() >= 1.0:
                mask = None
        if mask is None and pad_w is None:
            if self._ones_w is None or len(self._ones_w) != self.workers:
                self._ones_w = np.ones(self.workers, np.float32)
            return self._ones_w
        if mask is None:
            return pad_w
        if pad_w is None:
            return mask
        combined = mask * pad_w
        return combined if combined.sum() > 0 else mask

    def _stage(self, net, K, shard):
        """Stage the facade's trees onto the mesh: stacked ``[K, ...]``
        replicas (replicated mode) or the ZeRO layout — params + inner
        updater slots sharded 1/K per device, the reserved subtrees and
        net state stacked per replica as in replicated mode."""
        if self.update_sharding == zero_mod.ZERO:
            layout = self._zero_layout
            params_z = layout.place(net.params)
            upd_z = (layout.place_updater(
                net.updater_state,
                reserved_place=lambda t: jax.device_put(
                    _stack_tree(t, K), shard))
                if net.updater_state else {})
            ns_z = _stack_tree(net.net_state, K)
            if net.net_state:
                ns_z = jax.device_put(ns_z, shard)
            return params_z, upd_z, ns_z
        params_k = jax.device_put(_stack_tree(net.params, K), shard)
        upd_k = _stack_tree(net.updater_state, K)
        if net.updater_state:
            upd_k = jax.device_put(upd_k, shard)
        ns_k = _stack_tree(net.net_state, K)
        if net.net_state:
            ns_k = jax.device_put(ns_k, shard)
        return params_k, upd_k, ns_k

    def _fold_back(self, net, params_k, upd_k, ns_k, it, last_losses):
        """Fold the averaged replica-0 state back into the facade (loop
        end, window-boundary checkpoint saves, preemption stop).  Under
        ZeRO the params / inner updater leaves are already the single
        logical copy (sharded jax arrays — the facade, the checkpoint
        writer and ``net.output`` consume them directly); only the
        stacked reserved subtrees and net state take the replica-0
        slice."""
        if self.update_sharding == zero_mod.ZERO:
            net.params = params_k
            net.updater_state = {
                slot: (jax.tree_util.tree_map(lambda a: a[0], tree)
                       if slot in shardstats.RESERVED_REPLICATED_SUBTREES
                       else tree)
                for slot, tree in upd_k.items()}
            net.net_state = jax.tree_util.tree_map(lambda a: a[0], ns_k)
        else:
            net.params = jax.tree_util.tree_map(lambda a: a[0], params_k)
            net.updater_state = jax.tree_util.tree_map(lambda a: a[0],
                                                       upd_k)
            net.net_state = jax.tree_util.tree_map(lambda a: a[0], ns_k)
        if last_losses is not None:
            net.score_value = last_losses[-1].mean()  # device scalar; lazy
        net.iteration = it

    def phase_stats(self):
        """Per-phase wall-time aggregates of this wrapper's fit loop
        (same schema as ``TrainingMaster.training_stats()['phases']``)."""
        return self._phases.as_dict()

    # -- per-worker diagnosis ---------------------------------------------
    def _worker_step_times(self, losses, dispatch_s: float) -> Dict[str, float]:
        """Per-replica completion time of the last window: blocks on each
        replica's loss shard in device order and adds its arrival offset
        to the dispatch time.

        Measurement honesty: the window program ends in the parameter-
        averaging all-reduce, and a collective gates every device on the
        slowest one — so shard readiness reflects the CLUSTER critical
        path (the slow replica sets everyone's time), not per-replica
        blame, and the sequential poll means a slow first-polled shard
        masks later ones.  What this yields in-process is an accurate
        cluster step-time distribution (the thing SLO rules and p99s
        read).  Per-replica ATTRIBUTION comes from feeding
        ``WorkerTelemetry.observe`` with externally measured times — a
        multi-process driver timing its own host, a chaos harness, or
        the tests — through exactly this seam (override this method).
        When the loss is not addressably sharded per replica, the whole
        window is synced and its WALL time (dispatch + execution — not
        just the async enqueue time, which would report microsecond
        "steps" and wildly inflated throughput) is attributed to every
        worker."""
        K = self.workers

        def blocked_total() -> Dict[str, float]:
            t0 = time.perf_counter()
            try:
                jax.block_until_ready(losses)
            except Exception:
                pass
            total = dispatch_s + (time.perf_counter() - t0)
            return {str(k): total for k in range(K)}

        if losses is None:
            return {str(k): dispatch_s for k in range(K)}
        try:
            shards = list(losses.addressable_shards)
        except Exception:
            return blocked_total()
        if len(shards) < 2:
            return blocked_total()
        times = {str(k): dispatch_s for k in range(K)}
        t0 = time.perf_counter()
        for sh in shards:
            try:
                jax.block_until_ready(sh.data)
            except Exception:
                continue
            arrive = time.perf_counter() - t0
            idx = sh.index  # slices into the global [F, K] loss array
            if (isinstance(idx, tuple) and len(idx) >= 2
                    and isinstance(idx[1], slice)):
                for k in range(*idx[1].indices(K)):
                    times[str(k)] = dispatch_s + arrive
        return times

    def _publish_worker_stats(self, losses, dispatch_s: float,
                              wait_s: float, xs) -> None:
        from deeplearning4j_tpu.resilience import get_fault_injector

        F = max(1, int(xs.shape[0]))
        B = int(xs.shape[2]) if xs.ndim >= 3 else None
        times = self._worker_step_times(losses, dispatch_s)
        inj = get_fault_injector()
        if inj is not None:
            # deterministic chaos: an injected per-worker delay makes the
            # straggler detector's input reproducible in tests
            times = {w: t + inj.worker_delay(w) for w, t in times.items()}
        for worker, t in times.items():
            self._workers.observe(
                worker, t / F, batch=B,
                phases={"wait_window": wait_s / F, "dispatch": t / F})

    def cluster_stats(self) -> Dict[str, Any]:
        """Merged per-replica view (mean/p50/p99/max step time, slowest
        worker, total throughput) — empty before the first window or when
        ``collect_worker_stats=False``."""
        return self._workers.cluster_view() if self._workers else {}

    @property
    def straggler_detector(self):
        return self._workers.detector if self._workers else None

    def _stack_window(self, window, n_real=None):
        """Host half of a window step: pad + stack to [F, K, B, ...].
        Runs on the assembler thread, not the dispatch thread.

        ``n_real`` is the count of REAL minibatches in ``window`` — the
        assembler duplicates the tail minibatch to fill the last row of K
        replica slots, and those pad-filled slots must be weighted out of
        the window's parameter average or the duplicate is double-counted
        (the tail-window bias fix; ``_pad_weights``)."""
        K = self.workers
        F = len(window) // K
        # equalize batch sizes across the window (short/ragged final batches)
        max_b = max(len(w) for w in window)
        window = [w.pad_batch(max_b) if len(w) < max_b else w for w in window]
        xs = np.stack([np.stack([w.features for w in window[f * K : (f + 1) * K]]) for f in range(F)])
        ys = np.stack([np.stack([w.labels for w in window[f * K : (f + 1) * K]]) for f in range(F)])
        fms = self._stack_masks([w.features_mask for w in window], K, F)
        lms = self._stack_masks([w.labels_mask for w in window], K, F)
        n_real = len(window) if n_real is None else n_real
        return xs, ys, fms, lms, len(window), \
            self._pad_weights(n_real, len(window))

    def _pad_weights(self, n_real: int, n_slots: int):
        """[K] replica weights for a window whose minibatch slots past
        ``n_real`` are padding (duplicated tail batch in the generic path,
        zero-filled batches in the native path), or None when full.  Slot
        ``i`` belongs to replica ``i % K`` (rows are contiguous K-blocks),
        and the padding always lands in the last row, so a zero weight
        names exactly the replicas whose final scan step saw no real
        data."""
        if n_real >= n_slots:
            return None
        w = np.ones(self.workers, np.float32)
        for i in range(n_real, n_slots):
            w[i % self.workers] = 0.0
        return w

    def _native_windows(self, iterator):
        """Whole windows as single native gathers: the C++ producer thread
        assembles a contiguous [F*K*B] slab per window (row-major order
        matches the reference's sequential minibatch grouping).  The ragged
        tail honors the iterator's drop_last and is emitted as a TRUNCATED
        window — only as many (K-padded) batch rows as the data fills, with
        a labels mask on the zero-padded remainder — so iteration counts and
        score semantics track the generic path."""
        from deeplearning4j_tpu import native

        K, F = self.workers, self.averaging_frequency
        B = iterator.batch()
        data = iterator._data
        n = len(data)
        if getattr(iterator, "_drop_last", False):
            n = (n // B) * B  # generic path drops the ragged final batch
            if n == 0:
                return
            data = data.subset(slice(0, n))
        slab = B * K * F
        batcher = native.Batcher(data.features, data.labels, slab,
                                 shuffle=False, seed=1, drop_last=False,
                                 queue_cap=max(1, self.prefetch_size))
        try:
            while True:
                out = batcher.next()
                if out is None:
                    return
                feat, lab, n_valid = out
                if n_valid == slab:
                    xs = feat.reshape((F, K, B) + feat.shape[1:])
                    ys = lab.reshape((F, K, B) + lab.shape[1:])
                    yield xs, ys, None, None, F * K, None
                    continue
                # tail: keep only the batches the data actually fills, and
                # emit the FULL frames as their own window first (a
                # whole-tail per-replica weight would also discard those
                # replicas' real earlier minibatches from the average)
                nb = -(-n_valid // B)          # ceil: batches with any data
                f_full = nb // K               # complete K-replica frames
                mshape = ((nb * B,) if lab.ndim == 2
                          else (nb * B, lab.shape[1]))
                m = np.zeros(mshape, np.float32)
                m[:n_valid] = 1.0

                def part(lo_b, n_b, n_real_b):
                    """Window over batch slots [lo_b, lo_b + n_b)."""
                    rows = slice(lo_b * B, (lo_b + n_b) * B)
                    xs = feat[rows].reshape(
                        (n_b // K, K, B) + feat.shape[1:])
                    ys = lab[rows].reshape((n_b // K, K, B) + lab.shape[1:])
                    mp = np.zeros((n_b * B,) + m.shape[1:], np.float32)
                    avail = min(len(m) - lo_b * B, n_b * B)
                    if avail > 0:
                        mp[:avail] = m[lo_b * B:lo_b * B + avail]
                    lms = (None if mp.all() else jnp.asarray(
                        mp.reshape((n_b // K, K, B) + mp.shape[1:])))
                    # replicas whose batch slot is entirely zero padding
                    # are weighted out of the average: the labels mask
                    # already zeroes their LOSS, but a zero-grad step
                    # still mutates stateful updaters (Adam moments
                    # decay), so averaging their params back in would
                    # bias toward the pad
                    return (xs, ys, None, lms, n_b,
                            self._pad_weights(n_real_b - lo_b, n_b))

                if f_full:
                    yield part(0, f_full * K, nb)
                if nb % K:
                    yield part(f_full * K, K, nb)
        finally:
            batcher.close()

    @staticmethod
    def _stack_masks(masks, K, F):
        if all(m is None for m in masks):
            return None
        shaped = [np.asarray(m) for m in masks if m is not None]
        template = np.ones_like(shaped[0])
        masks = [np.asarray(m) if m is not None else template for m in masks]
        return jnp.asarray(
            np.stack([np.stack(masks[f * K : (f + 1) * K]) for f in range(F)])
        )
