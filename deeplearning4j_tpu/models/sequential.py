"""MultiLayerNetwork — the sequential-network facade.

Reference: ``nn/multilayer/MultiLayerNetwork.java`` (init :348, fit :1029,
feedForward :619-711, backprop :1085, TBPTT :1176, output :1525-1607,
rnnTimeStep :2195).  Functional redesign: params/state live in pytrees on
this facade; the training step is ONE jitted pure function
(loss -> jax.grad -> updater -> param update), replacing the reference's
Solver/StochasticGradientDescent object dance (``optimize/solvers/
StochasticGradientDescent.java:51-73``) with an XLA program.  The
reference's flattened-params invariant (single param vector,
``MultiLayerNetwork.java:97-98``) survives as ``params_to_vector`` /
``set_params_vector`` — used by serialization, param averaging, and tests.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.backend.rng import KeyStream
from deeplearning4j_tpu.models.common import (
    LazyScoreMixin, cast_to_compute, notify_listeners,
)
from deeplearning4j_tpu.observability import (
    crash_dump, fit_telemetry, instrument, step_guard,
)
from deeplearning4j_tpu.nn import losses as losses_mod
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.nn.layers.dense import OutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.optimize import updaters as upd


def _is_recurrent(layer) -> bool:
    return hasattr(layer, "apply_with_carry")


class MultiLayerNetwork(LazyScoreMixin):
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: Tuple[Layer, ...] = conf.layers
        self.params: Dict[str, Dict[str, jax.Array]] = {}
        self.net_state: Dict[str, Dict[str, jax.Array]] = {}
        self.updater_state: Dict[str, Any] = {}
        self.listeners: List[Any] = []
        self.iteration = 0
        self._score = None  # lazy score_value (LazyScoreMixin)
        self._keys = KeyStream(conf.seed)
        self._jit_cache: Dict[Any, Any] = {}
        self._stab_rt = None   # StabilityRuntime, created on first fit
        # streaming rnnTimeStep state: layer_name -> carry; _stream_pos is
        # the host-side mirror of the caches' device position scalar
        self._rnn_state: Dict[str, Any] = {}
        self._stream_pos: int = 0

    # ------------------------------------------------------------------ init
    def init(self, dtype=jnp.float32) -> "MultiLayerNetwork":
        params, net_state = {}, {}
        for layer in self.layers:
            if layer.has_params():
                params[layer.name] = layer.init(self._keys.next(), dtype)
            else:
                params[layer.name] = {}
            st = layer.init_state()
            if st:
                net_state[layer.name] = jax.tree_util.tree_map(
                    lambda a: a.astype(dtype), st
                )
        self.params = params
        self.net_state = net_state
        self.updater_state = upd.init_state(self.conf.updater, self._trainable(params))
        if self.conf.stability is not None:
            from deeplearning4j_tpu.resilience import stability

            # guard/scale state rides in the updater-state pytree: it
            # stacks, shards, donates, and checkpoints like Adam moments
            self.updater_state[stability.STATE_KEY] = (
                stability.initial_state(self.conf.stability))
        if self.conf.introspection is not None:
            from deeplearning4j_tpu.observability import introspection

            # per-layer stat vectors ride in the updater-state pytree
            # too: stacked per replica, replicated by the sync master,
            # donated, checkpointed (docs/observability.md)
            introspection.ensure_state(self)
        if self.conf.numerics is not None:
            from deeplearning4j_tpu.observability import numerics

            # precision ledger: same reserved-subtree transport
            numerics.ensure_state(self)
        return self

    def _trainable(self, params):
        return {k: v for k, v in params.items() if v}

    def num_params(self) -> int:
        # tree_leaves: composite layers (ResidualBlock) nest their params
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    # ----------------------------------------------------- flattened params
    def params_to_vector(self) -> np.ndarray:
        """Single flat param vector (reference flattenedParams invariant)."""
        leaves = jax.tree_util.tree_leaves(self.params)
        if not leaves:
            return np.zeros((0,), np.float32)
        return np.concatenate([np.asarray(l).reshape(-1) for l in leaves])

    def set_params_vector(self, vec: np.ndarray) -> None:
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        total = sum(int(np.prod(l.shape)) for l in leaves)
        if total != vec.size:
            raise ValueError(f"param vector size {vec.size} != model size {total}")
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(jnp.asarray(vec[off : off + n], l.dtype).reshape(l.shape))
            off += n
        self.params = jax.tree_util.tree_unflatten(treedef, out)

    # --------------------------------------------------------------- forward
    def _forward(self, params, net_state, x, *, train, rng, fmask=None,
                 carries=None, collect=False):
        """Pure forward through preprocessors + layers.

        Returns (last_pre_activation_input, activations list if collect,
        new_net_state, new_carries).  The output layer is applied EXCEPT its
        loss head; callers use layer.pre_output for scoring/inference.
        """
        acts = []
        new_state = dict(net_state)
        new_carries = {}
        h = x
        cd = self.conf.compute_dtype
        if cd is not None:
            # mixed precision: parameters and input in the compute dtype
            params, h = cast_to_compute((params, jnp.asarray(h)), cd)
        n = len(self.layers)
        rngs = jax.random.split(rng, n) if rng is not None else [None] * n
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                h = self.conf.preprocessors[i](h)
            lstate = net_state.get(layer.name, {})
            # the layer's name and kind on its device operations
            # (metadata only)
            with jax.named_scope(layer.name), layer.kind_scope():
                if _is_recurrent(layer):
                    carry = (carries or {}).get(layer.name)
                    h, lst, new_carry = layer.apply_with_carry(
                        params[layer.name], lstate, h, carry,
                        train=train, rng=rngs[i], mask=fmask,
                    )
                    new_carries[layer.name] = new_carry
                elif isinstance(layer, (OutputLayer,)):
                    # output head: stop at preoutput; activation applied
                    # on demand
                    h = self.maybe_flatten_time(layer, h)
                    h = layer.maybe_dropout(h, train=train, rng=rngs[i])
                    h = layer.pre_output(params[layer.name], h)
                else:
                    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
                    from deeplearning4j_tpu.nn.layers.composite import ResidualBlock
                    from deeplearning4j_tpu.nn.layers.convolution import GlobalPoolingLayer

                    mask_aware = (GlobalPoolingLayer, SelfAttentionLayer,
                                  ResidualBlock)
                    kw = ({"mask": fmask} if isinstance(layer, mask_aware)
                          else {})
                    h, lst = layer.apply(params[layer.name], lstate, h,
                                         train=train, rng=rngs[i], **kw)
                    if lst:
                        new_state[layer.name] = lst
            if collect:
                acts.append(h)
        return h, acts, new_state, new_carries

    @staticmethod
    def maybe_flatten_time(layer, h):
        return h

    # ----------------------------------------------------------------- score
    def _loss_fn(self, params, net_state, x, y, rng, fmask=None, lmask=None,
                 carries=None, train=True, collect_acts=False,
                 numerics_now=None):
        out_layer = self.layers[-1]
        if not isinstance(out_layer, OutputLayer):
            raise ValueError("Last layer must be an OutputLayer/RnnOutputLayer for fit()")
        pre, acts, new_state, new_carries = self._forward(
            params, net_state, x, train=train, rng=rng, fmask=fmask,
            carries=carries, collect=collect_acts
        )
        with jax.named_scope("loss"):
            if self.conf.compute_dtype is not None:
                pre = pre.astype(jnp.float32)  # loss in full precision
            data_loss = losses_mod.score(out_layer.loss, y, pre,
                                         out_layer.activation, lmask)
        reg = jnp.zeros(())
        for layer in self.layers:
            if layer.has_params():
                reg = reg + layer.reg_score(params[layer.name])
        if collect_acts:
            # introspection: summarize every layer's activations while
            # they are still live in the graph (reduced to [A] scalars
            # immediately — the full activations are never carried out)
            named = list(zip((l.name for l in self.layers), acts))
            policy = self.conf.introspection
            act_stats = {}
            if policy is not None:
                from deeplearning4j_tpu.observability import introspection

                act_stats = introspection.act_summary(
                    named, dead_eps=policy.dead_eps)
            npolicy = self.conf.numerics
            if npolicy is not None and npolicy.collect_activations:
                # precision ledger: activation dynamic-range blocks,
                # reduced in-graph the same way
                from deeplearning4j_tpu.observability import numerics

                act_stats.update(numerics.act_ranges(
                    named, policy=npolicy, now=numerics_now))
            return data_loss + reg, (new_state, new_carries, act_stats)
        return data_loss + reg, (new_state, new_carries)

    # ------------------------------------------------------------ train step
    def _step_core(self):
        """The raw (un-jitted) SGD step shared by the per-batch train step
        and the scanned multi-step window.  With ``conf.stability`` set,
        the step is wrapped by the non-finite guard: the loss is scaled
        before ``grad`` (mixed-precision loss scaling), gradients are
        unscaled and checked all-finite, and a poisoned step folds into a
        device-side no-op (``params = where(finite, new, old)``; updater
        and net state likewise) — zero host syncs, zero recompiles
        (resilience/stability.py).  ``stability=None`` keeps the exact
        pre-guard trace."""
        from deeplearning4j_tpu.observability import introspection, numerics

        updater_cfg = self.conf.updater
        policy = self.conf.stability
        plan = introspection.plan_for(self)
        nplan = numerics.plan_for(self)
        lr_overrides = {
            l.name: l.learning_rate for l in self.layers if l.learning_rate is not None
        }

        def step(params, upd_state, net_state, iteration, x, y, rng, fmask, lmask, carries):
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
                (loss, aux), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True
                )(params, net_state, x, y, rng, fmask, lmask, carries, **kw)
                new_net_state, new_carries, act_stats = (
                    numerics.unpack_aux(plan, nplan, aux))
                grads = {k: v for k, v in grads.items() if v}
                updates, new_upd_state = upd.update(
                    updater_cfg, grads, upd_state, iteration,
                    lr_overrides, params=params,
                )
                new_params = dict(params)
                for lname, u in updates.items():
                    new_params[lname] = upd.apply_updates(params[lname], u)
                introspection.attach(
                    new_upd_state, plan, grads=grads, params=params,
                    new_params=new_params, iteration=iteration,
                    act_stats=act_stats)
                numerics.attach(
                    new_upd_state, nplan, grads=grads, iteration=iteration,
                    act_stats=act_stats, prev=nstate, now=now)
                return new_params, new_upd_state, new_net_state, loss, new_carries
            from deeplearning4j_tpu.resilience import stability

            stab, inner = stability.split_state(upd_state)
            (_, (loss, aux)), grads = (
                jax.value_and_grad(
                    stability.scaled_loss(self._loss_fn, stab), has_aux=True
                )(params, net_state, x, y, rng, fmask, lmask, carries, **kw))
            new_net_state, new_carries, act_stats = (
                numerics.unpack_aux(plan, nplan, aux))
            new_params, new_upd_state, new_net_state, finite = (
                stability.apply_guarded_update(
                    policy, updater_cfg, stab, inner, params, net_state,
                    loss, grads, new_net_state, iteration, lr_overrides))
            # grads here are loss-scaled; norms unscale exactly
            introspection.attach(
                new_upd_state, plan, grads=grads, params=params,
                new_params=new_params, iteration=iteration,
                act_stats=act_stats, grad_scale=1.0 / stab["loss_scale"])
            numerics.attach(
                new_upd_state, nplan, grads=grads, iteration=iteration,
                act_stats=act_stats, grad_scale=1.0 / stab["loss_scale"],
                prev=nstate, now=now)
            if new_carries is not None and policy.skip_nonfinite:
                # a poisoned TBPTT window must not smuggle NaN hidden
                # state into the next window: reset the stream instead
                new_carries = stability.select(
                    finite, new_carries,
                    jax.tree_util.tree_map(jnp.zeros_like, new_carries))
            return new_params, new_upd_state, new_net_state, loss, new_carries

        return step

    def _make_train_step(self, with_carry: bool):
        return instrument(jax.jit(self._step_core(), donate_argnums=(0, 1, 2)),
                          "MultiLayerNetwork.train_step",
                          argnums=(3, 4, 5, 6, 7, 8, 9))

    def _make_scanned_step(self):
        """K weight updates in ONE dispatch: ``lax.scan`` over the step
        core.  Small models (LeNet-class) are dispatch-bound — the host
        floor per step dwarfs the compute — so the K-step window amortizes
        the floor to 1/K.
        XLA sees a static K-iteration loop: weights stay resident in HBM
        for the whole window, no host round-trips between updates."""
        core = self._step_core()

        def multi(params, upd_state, net_state, it0, xs, ys, rngs):
            def body(carry, inp):
                params, upd_state, net_state, it = carry
                x, y, rng = inp
                params, upd_state, net_state, loss, _ = core(
                    params, upd_state, net_state, it, x, y, rng,
                    None, None, None)
                return (params, upd_state, net_state, it + 1.0), loss

            (params, upd_state, net_state, _), losses = jax.lax.scan(
                body, (params, upd_state, net_state, it0), (xs, ys, rngs))
            return params, upd_state, net_state, losses

        return instrument(jax.jit(multi, donate_argnums=(0, 1, 2)),
                          "MultiLayerNetwork.scanned_step",
                          argnums=(3, 4, 5, 6))

    def fit_scanned(self, batches, scan_steps: int, epochs: int = 1):
        """Amortized training: consecutive same-shape minibatches are
        stacked ``scan_steps`` at a time and run as one scanned XLA program
        (see ``_make_scanned_step``).  Semantically identical to ``fit``
        over the same batches (same per-batch updates and RNG stream);
        listeners fire once per window, ``score_value`` is the window's
        last loss.  A short tail (< scan_steps batches, or a shape change)
        runs through the regular per-batch step.  SGD only — no masks,
        TBPTT, or solver paths."""
        if scan_steps < 1:
            raise ValueError(f"scan_steps={scan_steps} must be >= 1")
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            raise ValueError("fit_scanned requires SGD optimization")
        if self.conf.backprop_type == "truncated_bptt":
            raise ValueError("fit_scanned does not support TBPTT")
        if self.conf.num_iterations != 1:
            # fit() repeats each batch num_iterations times; the scan body
            # runs each batch once — diverging silently would betray the
            # 'semantically identical to fit' promise above
            raise ValueError("fit_scanned requires num_iterations == 1 "
                             f"(got {self.conf.num_iterations})")
        if self.conf.introspection is not None:
            from deeplearning4j_tpu.observability import introspection

            introspection.ensure_state(self)
            self._introspect_live = None
        if self.conf.numerics is not None:
            from deeplearning4j_tpu.observability import numerics

            numerics.ensure_state(self)
            self._numerics_live = None
        scanned = self._jit_cache.setdefault(
            "scanned_step", self._make_scanned_step())
        step = self._get_train_step()
        try:
            for _ in range(epochs):
                window: list = []
                for batch in batches:
                    x, y, fm, lm = self._unpack(batch)
                    if fm is not None or lm is not None:
                        raise ValueError("fit_scanned does not support masks")
                    x, y = np.asarray(x), np.asarray(y)
                    if window and (window[0][0].shape != x.shape
                                   or window[0][1].shape != y.shape):
                        self._flush_window(window, scanned, step, scan_steps)
                        window = []
                    window.append((x, y))
                    if len(window) == scan_steps:
                        self._flush_window(window, scanned, step, scan_steps)
                        window = []
                if window:
                    self._flush_window(window, scanned, step, scan_steps)
        except Exception as e:
            crash_dump("fit_exception", model="MultiLayerNetwork",
                       iteration=self.iteration, error=repr(e))
            raise
        return self

    def _flush_window(self, window, scanned, step, scan_steps):
        if len(window) == scan_steps:
            tel = fit_telemetry("MultiLayerNetwork")
            t0 = time.perf_counter()
            with step_guard("fit_window", model="MultiLayerNetwork",
                            iteration=self.iteration, steps=len(window)):
                with tel.span(self.iteration):
                    xs = jnp.asarray(np.stack([b[0] for b in window]))
                    ys = jnp.asarray(np.stack([b[1] for b in window]))
                    rngs = jnp.stack([self._keys.next() for _ in window])
                    it0 = jnp.asarray(self.iteration, jnp.float32)
                    (self.params, self.updater_state, self.net_state,
                     losses) = scanned(self.params, self.updater_state,
                                       self.net_state, it0, xs, ys, rngs)
            self.score_value = losses[-1]
            self.iteration += len(window)
            tel.record_step(time.perf_counter() - t0, len(window[0][0]),
                            losses[-1], steps=len(window), model=self)
            # listeners fire once per window, so they get the WINDOW's
            # sample count — samples/sec = samples / (window wall time)
            notify_listeners(self, len(window[0][0]) * len(window))
        else:   # short tail: regular per-batch step keeps semantics exact
            for x, y in window:
                self._one_step(step, x, y, None, None, carries=None)

    def _get_train_step(self, with_carry=False):
        key = ("train_step", with_carry)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._make_train_step(with_carry)
        return self._jit_cache[key]

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, *, fmask=None, lmask=None,
            epochs: int = 1, checkpoint_manager=None, retry_policy=None):
        """Train.  ``data`` is a DataSetIterator-style iterable of
        (features, labels[, fmask, lmask]) tuples, or a single (X, y) pair.
        Reference: ``MultiLayerNetwork.fit(DataSetIterator)`` :1029.

        With ``checkpoint_manager=`` the loop auto-resumes from the newest
        committed checkpoint (params/updater/RNG/iteration restored, the
        already-consumed batches skipped), saves on the manager's triggers
        at step boundaries, and — on SIGTERM/SIGINT via an installed
        ``PreemptionHandler`` — commits a priority checkpoint and returns
        cleanly.  ``retry_policy=`` retries transient step failures with
        backoff (docs/resilience.md)."""
        from deeplearning4j_tpu.observability import profiling, shardstats

        prof = profiling.active_profiler()
        if prof is not None:
            # memory attribution: flight/watchdog dumps show this model's
            # per-leaf param/updater byte breakdown (weakly held)
            prof.track_model(self, "MultiLayerNetwork")
        # sharding ledger (per-tree bytes/replication; metadata walk only,
        # once per fit call) — flight dumps and GET /memory read it
        shardstats.record_model_ledger(self, "MultiLayerNetwork")
        res = None
        if checkpoint_manager is not None or retry_policy is not None:
            from deeplearning4j_tpu.resilience import FitResilience

            res = FitResilience("MultiLayerNetwork", checkpoint_manager,
                                retry_policy, net=self)
        if self.conf.stability is not None:
            from deeplearning4j_tpu.resilience import stability

            stability.ensure_state(self)
            created = self._stab_rt is None
            if created:
                self._stab_rt = stability.StabilityRuntime(
                    "MultiLayerNetwork", self.conf.stability)
            if created or (res is not None and res.resumed_from is not None):
                # a restored nonfinite_total is history, not fresh evidence
                self._stab_rt.baseline_from(
                    self.updater_state.get(stability.STATE_KEY))
        if self.conf.introspection is not None:
            from deeplearning4j_tpu.observability import introspection

            introspection.ensure_state(self)
            # the facade's updater_state is authoritative during a solo
            # fit; a stale per-replica stamp from an earlier master run
            # must not shadow it
            self._introspect_live = None
        if self.conf.numerics is not None:
            from deeplearning4j_tpu.observability import numerics

            numerics.ensure_state(self)
            self._numerics_live = None
        try:
            if labels is not None:
                batches = [(data, labels, fmask, lmask)]
                self._fit_batches(batches, res)
                return self
            for _ in range(epochs):
                if self._fit_batches(data, res):
                    break   # preemption: stopped cleanly at a boundary
        except Exception as e:
            # fit-loop exception: leave the same flight-recorder report a
            # hang would (events + live spans + registry snapshot)
            crash_dump("fit_exception", model="MultiLayerNetwork",
                       iteration=self.iteration, error=repr(e))
            raise
        finally:
            if self._stab_rt is not None:
                # final harvest: the tail of the run past the last check
                # boundary still lands in the non-finite counter (early
                # stopping and health rules read it)
                self._stab_rt.flush(self)
        return self

    def _fit_batches(self, batches, res=None) -> bool:
        """One pass; returns True when preemption stopped the loop."""
        from deeplearning4j_tpu.resilience import preemption_requested

        if self.conf.optimization_algo != "stochastic_gradient_descent":
            for batch in batches:
                # the solver writes params/score and advances the iteration
                # by exactly 1 per batch, all AFTER the solve — so skip is
                # per batch and a whole-batch retry is state-safe
                if res is not None and res.skip_batch():
                    continue
                if preemption_requested():
                    if res is not None:
                        res.on_preempt(self)
                    return True
                x, y, fm, lm = self._unpack(batch)
                if res is not None:
                    res.step(lambda: self._fit_solver(x, y, fm, lm),
                             self.iteration, net=self)
                    res.after_step(self)
                else:
                    self._fit_solver(x, y, fm, lm)
            return False
        step = self._get_train_step()
        tbptt = self.conf.backprop_type == "truncated_bptt"
        L = self.conf.tbptt_fwd_length
        for batch in batches:
            x, y, fm, lm = self._unpack(batch)
            if res is not None:
                # skip is counted in ITERATIONS: one batch advances by
                # num_iterations, times the TBPTT window count for
                # sequence fits
                windows = -(-int(np.shape(x)[1]) // L) if tbptt else 1
                if res.skip_window(self.conf.num_iterations * windows):
                    continue
            if preemption_requested():
                if res is not None:
                    res.on_preempt(self)
                return True
            for _ in range(self.conf.num_iterations):
                if tbptt:
                    self._fit_tbptt(step, x, y, fm, lm, res)
                elif res is not None:
                    res.step(lambda: self._one_step(
                        step, x, y, fm, lm, carries=None),
                        self.iteration, net=self)
                else:
                    self._one_step(step, x, y, fm, lm, carries=None)
            if res is not None:
                res.after_step(self)
            if self._stab_rt is not None:
                # divergence sentinel: no-op except every check_every-th
                # boundary, where the device counter is harvested and an
                # escalation (LR backoff / checkpoint rewind) may land
                self._stab_rt.poll_net(self, res)
        return False

    def _fit_solver(self, x, y, fm, lm):
        """Full-batch solver path (CG/LBFGS/line-search GD) over the flat
        param vector.  Reference ``Solver.java:47-74`` dispatch +
        ``BaseOptimizer.java:165`` iterative optimize."""
        from deeplearning4j_tpu.optimize import solvers as solvers_mod

        args = (
            self.net_state, jnp.asarray(x), jnp.asarray(y), self._keys.next(),
            None if fm is None else jnp.asarray(fm),
            None if lm is None else jnp.asarray(lm),
        )

        def loss_fn(params, net_state, x, y, rng, fm, lm):
            return self._loss_fn(params, net_state, x, y, rng, fm, lm, None)

        solvers_mod.fit_model_with_solver(
            self, loss_fn, args, self.conf.optimization_algo,
            self.conf.num_iterations,
        )

    def _one_step(self, step, x, y, fm, lm, carries):
        from deeplearning4j_tpu.resilience import get_fault_injector

        inj = get_fault_injector()
        if inj is not None and inj.has_poison():
            # deterministic chaos: single-device fit loops poison under
            # worker id "0" (docs/resilience.md "Stability")
            x, y = inj.poison_batch("0", self.iteration, x, y)
        rng = self._keys.next()
        it = jnp.asarray(self.iteration, jnp.float32)
        tel = fit_telemetry("MultiLayerNetwork")
        t0 = time.perf_counter()
        with step_guard("fit_step", model="MultiLayerNetwork",
                        iteration=self.iteration):
            with tel.span(self.iteration):
                (self.params, self.updater_state, self.net_state, loss,
                 new_carries) = step(
                    self.params, self.updater_state, self.net_state, it,
                    jnp.asarray(x), jnp.asarray(y), rng,
                    None if fm is None else jnp.asarray(fm),
                    None if lm is None else jnp.asarray(lm),
                    carries,
                )
        self.score_value = loss  # device scalar; fetched lazily on read
        self.iteration += 1
        tel.record_step(time.perf_counter() - t0, int(np.shape(x)[0]), loss,
                        model=self)
        notify_listeners(self, int(np.shape(x)[0]))
        return new_carries

    def _fit_tbptt(self, step, x, y, fm, lm, res=None):
        """Truncated BPTT: slice the time axis into fwd-length windows,
        carrying RNN state (detached) across windows.
        Reference ``doTruncatedBPTT`` ``MultiLayerNetwork.java:1176``.

        The resilience retry scope is per WINDOW (each window is one
        iteration that already updated params — retrying a whole batch
        would replay committed windows)."""
        T = x.shape[1]
        L = self.conf.tbptt_fwd_length
        carries = None
        for t0 in range(0, T, L):
            sl = slice(t0, min(t0 + L, T))

            def one_window(c=carries, sl=sl):
                return self._one_step(
                    step, x[:, sl], y[:, sl],
                    None if fm is None else fm[:, sl],
                    None if lm is None else lm[:, sl],
                    c,
                )

            if res is not None:
                carries = res.step(one_window, self.iteration, net=self)
            else:
                carries = one_window()
            carries = jax.lax.stop_gradient(carries)

    @staticmethod
    def _unpack(batch):
        if isinstance(batch, (tuple, list)):
            if len(batch) == 2:
                return batch[0], batch[1], None, None
            if len(batch) == 4:
                return batch
        if hasattr(batch, "features"):
            return batch.features, batch.labels, getattr(batch, "features_mask", None), getattr(batch, "labels_mask", None)
        raise ValueError(f"Cannot unpack batch of type {type(batch)}")

    # ------------------------------------------------------------- inference
    def _output_fn(self):
        if "output" not in self._jit_cache:

            def out(params, net_state, x, fmask):
                pre, _, _, _ = self._forward(params, net_state, x, train=False,
                                             rng=None, fmask=fmask)
                if self.conf.compute_dtype is not None:
                    pre = pre.astype(jnp.float32)  # fp32 API boundary
                from deeplearning4j_tpu.nn import activations

                return activations.get(self.layers[-1].activation)(pre)

            self._jit_cache["output"] = jax.jit(out)
        return self._jit_cache["output"]

    def output(self, x, fmask=None):
        """Inference forward (reference ``output`` :1525-1607, TEST mode)."""
        return self._output_fn()(self.params, self.net_state, jnp.asarray(x),
                                 None if fmask is None else jnp.asarray(fmask))

    def feed_forward(self, x, train: bool = False):
        """All layer activations (reference ``feedForward`` :619-688)."""
        rng = self._keys.next() if train else None
        pre, acts, _, _ = self._forward(self.params, self.net_state,
                                        jnp.asarray(x), train=train, rng=rng,
                                        collect=True)
        if self.conf.compute_dtype is not None:
            acts = [a.astype(jnp.float32) for a in acts]  # fp32 API boundary
        return acts

    def evaluate(self, iterator, evaluation=None):
        """Run the iterator through ``output`` and accumulate classification
        metrics (reference ``MultiLayerNetwork.evaluate(DataSetIterator)``)."""
        from deeplearning4j_tpu.evaluation import Evaluation

        ev = evaluation or Evaluation()
        for ds in iterator:
            out = self.output(ds.features, fmask=ds.features_mask)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    def score(self, x=None, y=None, dataset=None, fmask=None, lmask=None) -> float:
        if dataset is not None:
            if hasattr(dataset, "features"):
                x, y = dataset.features, dataset.labels
                fmask = fmask if fmask is not None else getattr(dataset, "features_mask", None)
                lmask = lmask if lmask is not None else getattr(dataset, "labels_mask", None)
            else:
                x, y = dataset[0], dataset[1]
        loss, _ = self._loss_fn(self.params, self.net_state, jnp.asarray(x),
                                jnp.asarray(y), None, fmask, lmask, train=False)
        return float(loss)

    # ------------------------------------------------- streaming rnnTimeStep
    def rnn_clear_previous_state(self):
        self._rnn_state = {}
        self._stream_pos = 0

    def _embeds_ids(self) -> bool:
        """First layer consumes integer token ids (EmbeddingLayer), so a
        rank-2 streaming input is [B, T] ids, not [B, F] features."""
        from deeplearning4j_tpu.nn.layers.dense import EmbeddingLayer

        return bool(self.layers) and isinstance(self.layers[0], EmbeddingLayer)

    def rnn_time_step(self, x):
        """Stateful streaming inference (reference ``rnnTimeStep`` :2195):
        feeds one (or a few) timesteps, carries hidden state between calls.
        Recurrent layers carry hidden state; attention layers carry a KV
        cache (seeded on first call), so transformer stacks stream through
        the same API as LSTMs."""
        from deeplearning4j_tpu.models.common import (
            check_cache_capacity, seed_stream_caches,
        )

        x = jnp.asarray(x)
        if self._embeds_ids():
            collapse = self.layers[0].collapse_column
            # [B] ids are one timestep; with column semantics, so is [B, 1]
            # (the reference's column-of-indices form, which the old
            # streaming contract returned as [B, V])
            squeeze = x.ndim == 1 or (
                collapse and x.ndim == 2 and x.shape[1] == 1)
            if x.ndim == 1:
                x = x[:, None]
            if x.ndim == 2 and collapse:
                # [B, T, 1] keeps the time axis unambiguous for embeddings
                # that collapse a trailing 1 as a column-of-indices
                x = x[..., None]
        else:
            squeeze = x.ndim == 2          # [B, F]: one timestep of features
            if squeeze:
                x = x[:, None, :]
        if not self._rnn_state:
            self._stream_pos = 0
        carries = seed_stream_caches(
            ((l.name, l) for l in self.layers), self._rnn_state,
            x.shape[0], self.conf.compute_dtype)
        # host-side position counter: no device->host sync per streamed chunk
        check_cache_capacity(carries, int(x.shape[1]), pos=self._stream_pos)
        carries = carries or None
        pre, _, _, new_carries = self._forward(
            self.params, self.net_state, x, train=False, rng=None, carries=carries
        )
        self._rnn_state = new_carries
        self._stream_pos += int(x.shape[1])
        from deeplearning4j_tpu.nn import activations

        out = activations.get(self.layers[-1].activation)(pre)
        return out[:, -1] if squeeze and out.ndim == 3 else out

    # ------------------------------------------------------------- pretrain
    def pretrain(self, batches, epochs: int = 1):
        """Layerwise unsupervised pretraining (reference ``pretrain``
        ``MultiLayerNetwork.java:164``; RBM/AutoEncoder objectives)."""
        from deeplearning4j_tpu.nn.layers.autoencoder import AutoEncoder, RBM

        batches = list(batches) if not isinstance(batches, list) else batches
        for i, layer in enumerate(self.layers):
            if not isinstance(layer, (AutoEncoder, RBM)):
                continue

            def ploss(lparams, x, rng, _layer=layer):
                return _layer.pretrain_loss(lparams, x, rng)

            grad_fn = jax.jit(jax.value_and_grad(ploss))
            lr = layer.learning_rate or self.conf.updater.learning_rate
            for _ in range(epochs):
                for batch in batches:
                    # bare feature arrays are fine here: pretraining is
                    # unsupervised, labels are ignored even when present
                    x = jnp.asarray(batch if hasattr(batch, "ndim")
                                    else self._unpack(batch)[0])
                    # feed through earlier layers (test mode)
                    for j in range(i):
                        if j in self.conf.preprocessors:
                            x = self.conf.preprocessors[j](x)
                        x, _ = self.layers[j].apply(
                            self.params[self.layers[j].name],
                            self.net_state.get(self.layers[j].name, {}),
                            x, train=False, rng=None,
                        )
                    if i in self.conf.preprocessors:
                        x = self.conf.preprocessors[i](x)
                    loss, g = grad_fn(self.params[layer.name], x, self._keys.next())
                    self.params[layer.name] = jax.tree_util.tree_map(
                        lambda p, gg: p - lr * gg, self.params[layer.name], g
                    )
        return self

    # ------------------------------------------------------------- listeners
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    # ------------------------------------------------------------------ io
    def save(self, path, save_updater: bool = True):
        from deeplearning4j_tpu.models import serialization

        serialization.write_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.models import serialization

        return serialization.restore_multi_layer_network(path)

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(self.conf)
        net.params = jax.tree_util.tree_map(lambda a: a, self.params)
        net.net_state = jax.tree_util.tree_map(lambda a: a, self.net_state)
        net.updater_state = jax.tree_util.tree_map(lambda a: a, self.updater_state)
        net.iteration = self.iteration
        return net
