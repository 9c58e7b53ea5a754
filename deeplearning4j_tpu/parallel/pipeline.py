"""Pipeline parallelism — GPipe-style microbatching over a 'pipe' mesh axis.

Beyond-reference extension (SURVEY.md §2: PP absent in the reference).

Two execution paths:

- **Compiled** (the TPU path): when the net contains a periodic run of
  identical-structure layers (the transformer/MLP-block case every real
  pipeline targets), the ENTIRE schedule compiles to one XLA program —
  ``shard_map`` over a 1-D 'pipe' mesh, block params stacked [S, ...] and
  sharded stage-per-device, ``lax.scan`` over M + S - 1 ticks with
  ``lax.ppermute`` moving activations to the next stage each tick.  While
  microbatch m sits in stage s, microbatch m+1 computes in stage s-1 —
  the GPipe fill/drain diagram as dataflow inside the compiler, not as a
  Python loop: one compilation per config, no host-held pullbacks, and
  gradients flow through the ppermute chain via AD (its transpose is the
  reverse rotation).  Non-periodic head/tail layers run replicated, with
  their contributions masked to stage 0 / stage S-1 and grads psum'd.

- **Compiled heterogeneous** (round 4; params sharded round 5): NON-periodic
  stacks (the conv-then-dense case) also compile to one XLA program.  Under
  SPMD every device must run the same program, so the per-stage functions
  live in a ``lax.switch`` on ``lax.axis_index('pipe')``, and inter-stage
  activations — whose shapes differ between boundaries — travel as a flat
  buffer padded to the largest boundary, reshaped by each stage's branch.
  Params get the same flat-buffer treatment: each stage's tree is raveled
  into one f32 row, rows padded and stacked [S, Pmax] SHARDED over the pipe
  axis (optimizer state too), so per-device memory is ~1/S of the model —
  branch s unflattens its own row inside the switch, grads arrive on the
  owning device via the ppermute-transpose chain (no grad psum), and the
  elementwise updater acts on the rows directly (bitwise-identical to
  per-layer updates; guarded: no per-layer lr overrides / grad norm — with
  those set, params fall back to REPLICATED with a one-time stderr note).

- **Orchestrated** (explicit opt-in / fallback): per-stage ``jax.vjp``
  calls with real per-device param placement — partitions param memory for
  any net, at interpreter dispatch cost.  Supports both schedules:
  ``schedule='gpipe'`` (all forwards, then all backwards — M in-flight
  pullbacks) and ``schedule='1f1b'`` (backward of microbatch m follows its
  forward after the S-1 fill, PipeDream-flush style — at most S in-flight
  pullbacks, the activation-memory win; the bubble fraction is the same
  (S-1)/(M+S-1) as GPipe for non-interleaved stages).

Activation memory on the compiled paths: pass ``remat=True`` to
``jax.checkpoint`` each schedule tick — in one compiled program reverse-mode
AD stashes every tick's residuals regardless of schedule order (so a
compiled "1F1B" would buy nothing over GPipe); rematerializing the tick
body is the XLA-native equivalent of 1F1B's fewer-live-pullbacks win,
trading ~1 extra forward for O(1) residuals per tick.

Scope (all paths): sequential stateless nets (no BatchNorm running
stats, no masks, no TBPTT, no dropout).  Compose with DP/TP via those
masters; this one owns the pipe axis.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.common import notify_listeners
from deeplearning4j_tpu.observability import (
    PhaseTimers, WorkerTelemetry, instrument, step_guard,
)
from deeplearning4j_tpu.observability import shardstats
from deeplearning4j_tpu.optimize import updaters as upd
from deeplearning4j_tpu.parallel.training_master import TrainingMaster


def split_stages(net, n_stages: int) -> List[List[int]]:
    """Partition layer indices into n_stages contiguous groups minimizing
    the LARGEST stage's parameter count — the optimal contiguous partition
    (linear-partition DP, O(n² · S); n = layer count, trivially small).
    The max stage bounds both the pipeline's compute bottleneck tick and,
    on the sharded hetero path, per-device memory (Pmax), so min-max is
    the right objective (a greedy target-filling pass used to leave ~1.5x
    imbalance on mildly skewed stacks).  The reference has no analog;
    think layer-to-executor assignment."""
    counts = []
    for layer in net.layers:
        lp = net.params.get(layer.name, {})
        counts.append(sum(int(np.prod(a.shape)) for a in lp.values()) or 1)
    n = len(counts)
    n_stages = max(1, min(n_stages, n))
    prefix = np.concatenate([[0], np.cumsum(counts)])

    def seg(i, j):  # weight of layers[i:j]
        return prefix[j] - prefix[i]

    # best[k][j] = minimal max-stage weight splitting layers[:j] into k
    # stages; cut[k][j] = the last cut position achieving it
    INF = float(prefix[-1]) + 1.0
    best = [[INF] * (n + 1) for _ in range(n_stages + 1)]
    cut = [[0] * (n + 1) for _ in range(n_stages + 1)]
    best[0][0] = 0.0
    for k in range(1, n_stages + 1):
        for j in range(k, n + 1):
            for i in range(k - 1, j):
                cost = max(best[k - 1][i], float(seg(i, j)))
                if cost < best[k][j]:
                    best[k][j] = cost
                    cut[k][j] = i
    bounds = [n]
    for k in range(n_stages, 0, -1):
        bounds.append(cut[k][bounds[-1]])
    bounds.reverse()
    return [list(range(bounds[k], bounds[k + 1]))
            for k in range(n_stages)]


def _layer_sig(layer) -> str:
    """Structural signature: full layer config minus identity — two layers
    with equal signatures are interchangeable pipeline-stage material."""
    d = layer.to_dict()
    d.pop("name", None)
    return json.dumps(d, sort_keys=True)


def find_periodic_run(sigs: List[str], n_stages: int) -> Optional[Tuple[int, int, int]]:
    """Longest run ``layers[start : start + period * blocks]`` whose signature
    sequence repeats with ``period``, with ``blocks`` a positive multiple of
    ``n_stages``.  Returns (start, period, blocks) or None."""
    n = len(sigs)
    best = None
    for period in range(1, n // 2 + 1):
        for start in range(0, n - 2 * period + 1):
            blocks = 1
            while (start + (blocks + 1) * period <= n and
                   sigs[start + blocks * period : start + (blocks + 1) * period]
                   == sigs[start : start + period]):
                blocks += 1
            blocks -= blocks % n_stages
            if blocks >= n_stages and blocks >= 2:
                size = blocks * period
                if best is None or size > best[1] * best[2]:
                    best = (start, period, blocks)
    return best


def measure_bubble_fraction(make_net, make_batch, n_stages: int,
                            mb_size: int, m_small: int = 2,
                            m_large: int = 8, iters: int = 5,
                            devices: Optional[Sequence] = None,
                            mode: str = "auto") -> Dict[str, float]:
    """Measured pipeline bubble on a real mesh (the analytic counterpart is
    ``PipelineParallelTrainingMaster.bubble_fraction``).

    Holds the microbatch SIZE fixed and times steady-state steps at two
    microbatch COUNTS: t(M) ≈ (M + S - 1)·tick + c, so the slope between
    the two isolates the per-tick cost and ``(t - M·tick) / t`` is the
    fraction of the step not doing useful microbatch work (fill/drain
    bubble + fixed overhead c — updater, reg, dispatch; both are honest
    non-useful time).  ``make_net() -> net``, ``make_batch(n) -> DataSet``.
    """
    import time as _time

    def run(M):
        net = make_net()
        master = PipelineParallelTrainingMaster(
            n_stages=n_stages, n_microbatches=M, devices=devices, mode=mode)
        ds = make_batch(M * mb_size)
        master.execute_training(net, [ds])      # build + compile
        float(net.score_value)                  # block
        t0 = _time.perf_counter()
        master.execute_training(net, [ds] * iters)
        float(net.score_value)
        return (_time.perf_counter() - t0) / iters, master

    t_small, _ = run(m_small)
    t_large, master = run(m_large)
    tick = (t_large - t_small) / (m_large - m_small)
    measured = (t_large - m_large * tick) / t_large if t_large > 0 else 0.0
    return {
        "n_stages": n_stages,
        "mode": master._mode,
        "m_small": m_small, "m_large": m_large,
        "t_small_ms": round(t_small * 1e3, 3),
        "t_large_ms": round(t_large * 1e3, 3),
        "tick_ms": round(tick * 1e3, 3),
        "bubble_measured": round(measured, 4),
        "bubble_analytic": round(master.bubble_fraction(), 4),
    }


class PipelineParallelTrainingMaster(TrainingMaster):
    def __init__(self, n_stages: Optional[int] = None,
                 n_microbatches: int = 4,
                 devices: Optional[Sequence] = None,
                 schedule: str = "gpipe",
                 mode: str = "auto",
                 remat: bool = False,
                 checkpoint_manager=None,
                 retry_policy=None):
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"schedule={schedule!r}: use 'gpipe' or '1f1b'")
        if mode not in ("auto", "compiled", "orchestrated"):
            raise ValueError(
                f"mode={mode!r}: use 'auto', 'compiled' or 'orchestrated'")
        if remat and mode == "orchestrated":
            raise ValueError(
                "remat applies only to the compiled schedules (it "
                "jax.checkpoint's the compiled tick); the orchestrated "
                "path holds per-microbatch pullbacks instead — use "
                "schedule='1f1b' there for the activation-memory win")
        self.devices = list(devices if devices is not None else jax.devices())
        self.n_stages = n_stages or len(self.devices)
        if self.n_stages > len(self.devices):
            raise ValueError(
                f"{self.n_stages} stages > {len(self.devices)} devices")
        self.n_microbatches = n_microbatches
        self.schedule = schedule
        self.mode = mode
        # remat: jax.checkpoint each schedule tick in the COMPILED paths —
        # the XLA-native counterpart of 1F1B's activation-memory win.  In
        # one compiled program reverse-mode AD stashes every tick's
        # residuals (all M + S - 1 of them) regardless of schedule order,
        # so reordering backwards 1F1B-style buys nothing; what shrinks
        # live memory is rematerializing the tick body on the backward
        # pass, trading ~1 extra forward for O(1) residuals per tick.
        self.remat = remat
        self._built = False
        # registry-backed phase timers: whole-step dispatch on the compiled
        # paths; per-stage forward/backward dispatch on the orchestrated one
        self._phases = PhaseTimers("pipeline_master")
        # orchestrated path: per-STAGE step time published as
        # dl4j_worker_step_seconds{component="pipeline_master",
        # worker="stage<s>"} — stage imbalance is the pipeline's straggler
        # (the max stage bounds the bottleneck tick).  The compiled paths
        # run all stages inside one XLA program, so there is no per-stage
        # host timing to publish there.
        self._workers: Optional[WorkerTelemetry] = None
        # resilience wiring (docs/resilience.md): auto-resume on entry,
        # step-boundary saves (stage params folded back into the facade
        # only when a save is due), clean preemption stop, transient retry
        self.checkpoint_manager = checkpoint_manager
        self.retry_policy = retry_policy

    def _warn_fast_path_downgrade(self, reasons) -> None:
        """One-shot (per master) warning + flight event when the updater
        config knocks this net off the sharded fast path: param placement
        degrades from stage-per-device to fully replicated, so per-device
        memory silently holds the WHOLE model."""
        if getattr(self, "_downgrade_warned", False):
            return
        self._downgrade_warned = True
        import warnings

        from deeplearning4j_tpu.observability import get_flight_recorder

        why = "; ".join(reasons)
        warnings.warn(
            f"pipeline master: sharded param fast path DISABLED by {why} — "
            "params are replicated on every stage device (full-model "
            "memory per device).  Use mode='orchestrated' for partitioned "
            "placement, or drop the non-elementwise updater options "
            "(docs/PARALLELISM.md).", RuntimeWarning, stacklevel=3)
        get_flight_recorder().record(
            "pipeline_fast_path_downgrade", component="pipeline_master",
            reasons=reasons, n_stages=self.n_stages, mode=self.mode)

    def training_stats(self) -> Dict[str, Any]:
        """Phase-timed stats: whole-step ``dispatch`` on the compiled paths,
        ``stage{s}_fwd``/``stage{s}_bwd`` dispatch on the orchestrated one
        (same schema as the other masters; also in the registry as
        ``dl4j_phase_seconds{component="pipeline_master"}``)."""
        out = self._phases.as_dict()
        if self._workers is not None:
            out["cluster"] = self._workers.cluster_view()
        return out

    def bubble_fraction(self) -> float:
        """Analytic pipeline bubble: of the M + S - 1 schedule ticks, S - 1
        are fill/drain — identical for GPipe and non-interleaved 1F1B (1F1B
        buys activation MEMORY, not bubble).  Measured counterpart:
        ``measure_bubble_fraction``."""
        s = self.n_stages
        return (s - 1) / (self.n_microbatches + s - 1)

    # ------------------------------------------------------------ validation
    def _validate(self, net):
        if net.conf.backprop_type == "truncated_bptt":
            raise ValueError("pipeline master does not support TBPTT")
        if not hasattr(net.layers[-1], "score"):
            # every path (compiled, hetero, orchestrated) computes the loss
            # through the tail layer's score(); fail here with guidance
            # instead of deep inside a stage function
            raise ValueError(
                f"pipeline master needs the net to end in an output layer "
                f"with a score() (OutputLayer/RnnOutputLayer); got "
                f"'{net.layers[-1].name}' ({type(net.layers[-1]).__name__})")
        for layer in net.layers:
            if layer.init_state():
                raise ValueError(
                    f"pipeline master needs stateless layers; '{layer.name}' "
                    f"({type(layer).__name__}) carries state")
            if layer.dropout > 0:
                raise ValueError("pipeline master does not support dropout")

    # ------------------------------------------------------------- stage fns
    def _build(self, net):
        self._validate(net)
        self._mode = "orchestrated"
        cfg = net.conf.updater
        lr_overrides = {l.name: l.learning_rate for l in net.layers
                        if l.learning_rate is not None}
        if self.mode == "compiled" and self.n_stages < 2:
            raise ValueError("mode='compiled' needs n_stages >= 2 "
                             f"(got {self.n_stages})")
        if self.mode != "orchestrated" and self.n_stages > 1:
            # param sharding (periodic stacked OR hetero flat rows) is only
            # exact when the updater math is purely per-element: no
            # per-layer lr overrides, no per-layer grad-norm reductions
            elementwise_updater = (
                not lr_overrides
                and cfg.gradient_normalization in (None, "none"))
            if not elementwise_updater:
                # make the downgrade LOUD: these configs silently fell off
                # the sharded fast path onto replicated params (full model
                # per device) with nothing in logs or flight data naming
                # the cause (docs/PARALLELISM.md "Sharded fast path")
                self._warn_fast_path_downgrade(
                    ([f"gradient_normalization="
                      f"{cfg.gradient_normalization!r}"]
                     if cfg.gradient_normalization not in (None, "none")
                     else [])
                    + (["per-layer learning-rate overrides: "
                        + ", ".join(sorted(lr_overrides))]
                       if lr_overrides else []))
            # best path: periodic run -> stacked params SHARDED stage-per-
            # device (param memory partitioned)
            if elementwise_updater:
                run = find_periodic_run([_layer_sig(l) for l in net.layers],
                                        self.n_stages)
                if (run is not None
                        and run[0] + run[1] * run[2] < len(net.layers)):
                    self._build_compiled(net, run)
                    self._built = True
                    return
            # heterogeneous stacks still compile (switch-per-stage, padded
            # activation buffer — module docstring).  Params SHARD over the
            # pipe axis (flat-concat-pad rows, one per stage) under the
            # same elementwise guard; otherwise they stay replicated,
            # which is a per-device MEMORY cost worth flagging once.
            shard_params = elementwise_updater
            if not shard_params and self.mode == "auto":
                import sys as _sys
                print(
                    "pipeline note: auto mode compiled this non-periodic "
                    "net with REPLICATED params (per-layer lr overrides / "
                    "gradient normalization prevent the sharded flat "
                    "layout); per-device memory holds the full model — use "
                    "mode='orchestrated' for partitioned placement",
                    file=_sys.stderr)
            self._build_compiled_hetero(net, shard_params=shard_params)
            self._built = True
            return
        if self.remat:  # reachable only via n_stages == 1 (auto/compiled)
            import sys as _sys
            print("pipeline note: remat=True has no effect on the "
                  "orchestrated path (single-stage resolution); it applies "
                  "to the compiled schedules only", file=_sys.stderr)
        self.stages = split_stages(net, self.n_stages)
        self.stage_layers = [[net.layers[i] for i in s] for s in self.stages]
        out_layer = net.layers[-1]
        pre = net.conf.preprocessors

        def make_stage_fwd(idxs, layers):
            def fwd(stage_params, a):
                for gi, layer in zip(idxs, layers):
                    if gi in pre:
                        a = pre[gi](a)
                    a, _ = layer.apply(
                        stage_params[layer.name] if layer.has_params() else {},
                        {}, a, train=True, rng=None)
                return a
            return fwd

        def make_last_stage(idxs, layers):
            body = list(zip(idxs[:-1], layers[:-1]))

            def fwd_loss(stage_params, a, y):
                for gi, layer in body:
                    if gi in pre:
                        a = pre[gi](a)
                    p = stage_params.get(layer.name, {})
                    a, _ = layer.apply(p, {}, a, train=True, rng=None)
                if idxs[-1] in pre:
                    a = pre[idxs[-1]](a)
                return out_layer.score(stage_params[out_layer.name], a, y)
            return fwd_loss

        self._stage_fwds = [jax.jit(make_stage_fwd(idxs, ls))
                            for idxs, ls in zip(self.stages[:-1],
                                                self.stage_layers[:-1])]
        self._last_stage = jax.jit(make_last_stage(self.stages[-1],
                                                   self.stage_layers[-1]))
        self._reg_fns = [
            jax.jit(jax.value_and_grad(lambda sp, ls=ls: sum(
                layer.reg_score(sp.get(layer.name, {})) for layer in ls)))
            for ls in self.stage_layers
        ]
        cfg = net.conf.updater
        self._lr_overrides = {
            l.name: l.learning_rate for l in net.layers
            if l.learning_rate is not None
        }
        self._upd_cfg = cfg
        self._built = True

    def _stage_params(self, net, s: int) -> Dict[str, Any]:
        names = [net.layers[i].name for i in self.stages[s]]
        return {n: net.params[n] for n in names if n in net.params}

    # ------------------------------------------------------ compiled schedule
    def _build_compiled(self, net, run):
        """One-XLA-program GPipe: see module docstring.  Layers split as
        prefix | S stages x (blocks/S x period layers) | suffix; block params
        stack to [S, ...] leaves sharded over the 'pipe' mesh axis."""
        start, period, blocks = run
        S = self.n_stages
        per_stage = (blocks // S) * period
        seg = list(net.layers[start : start + blocks * period])
        self._pfx = list(net.layers[:start])
        self._sfx = list(net.layers[start + blocks * period:])
        self._stage_groups = [seg[s * per_stage : (s + 1) * per_stage]
                              for s in range(S)]
        self._template = self._stage_groups[0]
        from deeplearning4j_tpu.nn.layers.dense import OutputLayer as _Out

        if not self._sfx or not isinstance(self._sfx[-1], _Out):
            raise ValueError("pipeline suffix must end in an OutputLayer")
        self._mesh = Mesh(np.asarray(self.devices[:S]), ("pipe",))
        self._blk_sharding = NamedSharding(self._mesh, P("pipe"))
        self._repl_sharding = NamedSharding(self._mesh, P())
        self._upd_cfg = net.conf.updater
        self._mode = "compiled"
        self._compiled_kind = "periodic"
        self._compiled_steps = {}  # (xs.shape, ys.shape) -> jitted step

    # ------------------------------------- compiled heterogeneous schedule
    def _build_compiled_hetero(self, net, shard_params: bool = False):
        """One-XLA-program GPipe for NON-periodic stacks: stage bodies in a
        ``lax.switch`` on the pipe index, boundary activations in a flat
        padded buffer.  With ``shard_params`` (the default whenever the
        updater is exactly elementwise), each stage's param tree is raveled
        and concatenated into one f32 row, rows padded to the largest stage
        and stacked [S, Pmax] SHARDED over the pipe axis — per-device param
        (and optimizer-state) memory is ~1/S of the model, the same
        partitioning the periodic path gets from stacking, applied to
        heterogeneous trees via the flat buffer trick the activations
        already use.  Otherwise params stay replicated (see module
        docstring)."""
        self.stages = split_stages(net, self.n_stages)
        self.stage_layers = [[net.layers[i] for i in s] for s in self.stages]
        S = len(self.stages)
        self.n_stages = S
        self._mesh = Mesh(np.asarray(self.devices[:S]), ("pipe",))
        self._repl_sharding = NamedSharding(self._mesh, P())
        self._row_sharding = NamedSharding(self._mesh, P("pipe"))
        self._upd_cfg = net.conf.updater
        self._lr_overrides = {l.name: l.learning_rate for l in net.layers
                              if l.learning_rate is not None}
        self._mode = "compiled"
        self._compiled_kind = "hetero"
        self._hetero_sharded = shard_params
        if shard_params:
            self._flat_specs, self._flat_pmax = self._hetero_flat_spec(net)
        self._compiled_steps = {}

    def _hetero_flat_spec(self, net):
        """Per-stage flatten layout: (layer, path, shape, dtype, offset,
        size) per leaf, in deterministic (layer order, sorted path) order;
        returns (specs, Pmax)."""
        def leaves(d, prefix=()):
            out = []
            for k in sorted(d):
                v = d[k]
                if isinstance(v, dict):
                    out.extend(leaves(v, prefix + (k,)))
                else:
                    out.append((prefix + (k,), v))
            return out

        specs, sizes = [], []
        for ls in self.stage_layers:
            spec, off = [], 0
            for l in ls:
                for path, a in leaves(net.params.get(l.name, {}) or {}):
                    n = int(np.prod(a.shape))
                    spec.append((l.name, path, tuple(a.shape),
                                 jnp.dtype(a.dtype), off, n))
                    off += n
            specs.append(spec)
            sizes.append(off)
        return specs, max(max(sizes), 1)

    def _hetero_flatten(self, per_layer, missing_ok: bool = False):
        """Per-layer tree -> [S, Pmax] f32 rows (host side).  With
        ``missing_ok`` absent leaves flatten to zeros (fresh optimizer
        state)."""
        rows = np.zeros((len(self._flat_specs), self._flat_pmax), np.float32)
        for s, spec in enumerate(self._flat_specs):
            for lname, path, shape, dtype, off, n in spec:
                node = per_layer.get(lname, {})
                for k in path:
                    node = node.get(k, {}) if isinstance(node, dict) else {}
                if isinstance(node, dict):
                    if not missing_ok:
                        raise KeyError(f"missing param {lname}/{path}")
                    continue
                rows[s, off:off + n] = np.asarray(
                    node, np.float32).reshape(-1)
        return jnp.asarray(rows)

    def _hetero_unflatten_host(self, rows) -> Dict[str, Any]:
        """[S, Pmax] rows -> per-layer tree (host side, original dtypes)."""
        rows = np.asarray(rows)
        out: Dict[str, Any] = {}
        for s, spec in enumerate(self._flat_specs):
            for lname, path, shape, dtype, off, n in spec:
                node = out.setdefault(lname, {})
                for k in path[:-1]:
                    node = node.setdefault(k, {})
                node[path[-1]] = jnp.asarray(
                    rows[s, off:off + n].reshape(shape).astype(dtype))
        return out

    def _hetero_stage_tree(self, s: int, flat):
        """Unflatten ONE stage's tree from its local flat row (traced)."""
        out: Dict[str, Any] = {}
        for lname, path, shape, dtype, off, n in self._flat_specs[s]:
            node = out.setdefault(lname, {})
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = flat[off:off + n].reshape(shape).astype(dtype)
        return out

    def _make_hetero_step(self, net, x_mb_shape, x_dtype):
        S = len(self.stage_layers)
        M = self.n_microbatches
        cfg = self._upd_cfg
        stage_layers = self.stage_layers
        stage_idxs = self.stages
        out_layer = stage_layers[-1][-1]
        pre = net.conf.preprocessors

        def stage_fwd(s, tree, a):
            n = len(stage_layers[s]) - (1 if s == S - 1 else 0)
            for j in range(n):
                gi = stage_idxs[s][j]
                if gi in pre:
                    a = pre[gi](a)
                a, _ = stage_layers[s][j].apply(
                    tree.get(stage_layers[s][j].name, {}), {}, a,
                    train=True, rng=None)
            if s == S - 1 and stage_idxs[s][-1] in pre:
                a = pre[stage_idxs[s][-1]](a)   # preprocessor feeding the head
            return a

        # boundary shapes: output of stage s == input of stage s + 1
        bound = []
        probe = jax.ShapeDtypeStruct(x_mb_shape, x_dtype)
        for s in range(S - 1):
            probe = jax.eval_shape(
                lambda tr, a, s=s: stage_fwd(s, tr, a), net.params, probe)
            bound.append(probe)
        buf_dtype = jnp.result_type(*[b.dtype for b in bound])
        buf = max(int(np.prod(b.shape)) for b in bound)

        def schedule_loss(tree_for, xs, ys, idx):
            """The GPipe tick scan for ONE device's stage(s).  ``tree_for(s)``
            is called INSIDE branch s — with sharded params it unflattens
            the device's own row there, so only the taken branch's stage
            tree ever materializes (lax.switch executes one branch); the
            ppermute stays OUTSIDE the switch (collectives must sit at a
            uniform program point across devices)."""
            perm = [(i, i + 1) for i in range(S - 1)]

            def make_branch(s):
                def br(state, t):
                    tree = tree_for(s)
                    if s == 0:
                        a = xs[jnp.clip(t, 0, M - 1)]
                    else:
                        b = bound[s - 1]
                        n = int(np.prod(b.shape))
                        a = state[:n].reshape(b.shape).astype(b.dtype)
                    a = stage_fwd(s, tree, a)
                    if s == S - 1:
                        m_out = t - (S - 1)
                        l = out_layer.score(
                            tree.get(out_layer.name, {}), a,
                            ys[jnp.clip(m_out, 0, M - 1)])
                        return (jnp.zeros((buf,), buf_dtype),
                                l.astype(jnp.float32))
                    flat = a.reshape(-1).astype(buf_dtype)
                    return (jnp.pad(flat, (0, buf - flat.shape[0])),
                            jnp.zeros((), jnp.float32))
                return br

            branches = [make_branch(s) for s in range(S)]
            state0 = lax.pcast(jnp.zeros((buf,), buf_dtype), ("pipe",),
                               to="varying")
            loss0 = lax.pcast(jnp.zeros(()), ("pipe",), to="varying")

            def run_tick(state, t):
                return lax.switch(idx, branches, state, t)

            if self.remat:  # O(1) residuals per tick; ppermute stays out
                run_tick = jax.checkpoint(run_tick)

            def tick(carry, t):
                state, loss_sum = carry
                out, l = run_tick(state, t)
                m_out = t - (S - 1)
                loss_sum = loss_sum + jnp.where(
                    (idx == S - 1) & (m_out >= 0), l, 0.0)
                state = lax.ppermute(out, "pipe", perm)
                return (state, loss_sum), None

            (_, loss_sum), _ = lax.scan(
                tick, (state0, loss0), jnp.arange(M + S - 1))
            # LOCAL loss only (nonzero on the last stage); grads are
            # nonzero only for the executing stage's branch
            return loss_sum / M

        if self._hetero_sharded:
            return self._finish_hetero_sharded_step(schedule_loss, cfg, S)

        def spmd(tree, xs, ys):
            idx = lax.axis_index("pipe")
            loss, grads = jax.value_and_grad(
                lambda tr: schedule_loss(lambda s: tr, xs, ys, idx))(tree)
            # the psum reassembles the full tree without double counting
            return lax.psum(loss, "pipe"), lax.psum(grads, "pipe")

        repl = P()
        sharded = jax.shard_map(spmd, mesh=self._mesh,
                            in_specs=(repl, repl, repl),
                            out_specs=(repl, repl), check_vma=False)
        reg_layers = [l for ls in stage_layers for l in ls if l.has_params()]

        def reg_fn(tree):
            r = jnp.zeros(())
            for l in reg_layers:
                r = r + l.reg_score(tree.get(l.name, {}))
            return r

        lr_overrides = self._lr_overrides

        def step(tree, opt_state, it, xs, ys):
            loss, grads = sharded(tree, xs, ys)
            reg_val, reg_g = jax.value_and_grad(reg_fn)(tree)
            grads = {k: v for k, v in grads.items() if v}
            grads = jax.tree_util.tree_map(
                jnp.add, grads, {k: reg_g[k] for k in grads})
            updates, new_opt = upd.update(cfg, grads, opt_state, it,
                                          lr_overrides, params=tree)
            new_tree = {
                k: (upd.apply_updates(v, u)
                    if (u := updates.get(k)) else v)
                for k, v in tree.items()
            }
            return new_tree, new_opt, loss + reg_val

        return instrument(jax.jit(step, donate_argnums=(0, 1)),
                          "PipelineParallelTrainingMaster.hetero_step", argnums=(2, 3, 4))

    def _finish_hetero_sharded_step(self, schedule_loss, cfg, S):
        """Sharded-param variant: each device owns one [Pmax] f32 row
        holding its stage's raveled params; branch s unflattens ITS row.
        Grads w.r.t. the local row arrive via the ppermute-transpose chain
        with support only on the owning device — no grad psum at all (the
        dp all-reduce's absence is the point: pipe-axis traffic is
        activations + their cotangents only).  The elementwise updater then
        acts directly on the sharded [S, Pmax] rows (one pseudo-layer),
        bitwise-identical to per-layer updates because sgd/nesterov/adam/
        etc. are per-element — guarded upstream: no lr overrides, no
        gradient normalization."""
        stage_layers = self.stage_layers

        def spmd(flat_rows, xs, ys):
            idx = lax.axis_index("pipe")

            def local_total(flat):
                # branch s unflattens MY row as stage s's tree INSIDE the
                # switch branch — correct on the one device whose idx == s,
                # never materialized elsewhere
                loss = schedule_loss(
                    lambda s: self._hetero_stage_tree(s, flat), xs, ys, idx)

                def make_reg(s):
                    def rb(flat):
                        tree = self._hetero_stage_tree(s, flat)
                        r = jnp.zeros(())
                        for l in stage_layers[s]:
                            if l.has_params():
                                r = r + l.reg_score(tree.get(l.name, {}))
                        return r
                    return rb

                return loss + lax.switch(
                    idx, [make_reg(s) for s in range(S)], flat)

            loss, gflat = jax.value_and_grad(local_total)(flat_rows[0])
            return lax.psum(loss, "pipe"), gflat[None]

        sharded = jax.shard_map(spmd, mesh=self._mesh,
                            in_specs=(P("pipe"), P(), P()),
                            out_specs=(P(), P("pipe")), check_vma=False)

        def step(flat, opt_state, it, xs, ys):
            loss, gflat = sharded(flat, xs, ys)
            updates, new_opt = upd.update(
                cfg, {"_pipe": {"w": gflat}}, opt_state, it, {},
                params={"_pipe": {"w": flat}})
            return flat - updates["_pipe"]["w"], new_opt, loss

        return instrument(jax.jit(step, donate_argnums=(0, 1)),
                          "PipelineParallelTrainingMaster.hetero_step", argnums=(2, 3, 4))

    def _execute_hetero(self, net, iterator, res=None):
        from deeplearning4j_tpu.resilience import preemption_requested

        M = self.n_microbatches
        if self._hetero_sharded:
            # flat f32 rows, one per stage, device s owns row s — params
            # AND optimizer state partitioned ~1/S per device
            tree = jax.device_put(self._hetero_flatten(net.params),
                                  self._row_sharding)
            opt_state = {
                k: {"_pipe": {"w": jax.device_put(
                    self._hetero_flatten(per_layer, missing_ok=True),
                    self._row_sharding)}}
                for k, per_layer in net.updater_state.items()}
        else:
            tree = jax.device_put(net.params, self._repl_sharding)
            opt_state = jax.device_put(net.updater_state,
                                       self._repl_sharding)
        # the ledger makes the sharded-vs-replicated fast-path decision
        # visible: downgraded runs show replication_factor ≈ n_stages
        shardstats.record_ledger(
            "pipeline_master", {"params": tree, "updater_state": opt_state},
            data_axis_size=self.n_stages)

        def unflatten_back():
            if self._hetero_sharded:
                net.params.update(self._hetero_unflatten_host(tree))
                for k in net.updater_state:
                    net.updater_state[k].update(self._hetero_unflatten_host(
                        opt_state[k]["_pipe"]["w"]))
            else:
                net.params = tree
                net.updater_state = opt_state

        stopped = False
        for ds in iterator:
            if res is not None and res.skip_batch():
                continue   # auto-resume: batch already covered by the ckpt
            if preemption_requested():
                stopped = True
                break
            if ds.features_mask is not None or ds.labels_mask is not None:
                raise ValueError(
                    "pipeline master does not support masked batches")
            x = np.asarray(ds.features)
            y = np.asarray(ds.labels)
            if len(x) % M:
                raise ValueError(f"batch {len(x)} not divisible by "
                                 f"{M} microbatches")
            xs = jnp.asarray(x.reshape((M, len(x) // M) + x.shape[1:]))
            ys = jnp.asarray(y.reshape((M, len(y) // M) + y.shape[1:]))
            key = (xs.shape, ys.shape)
            if key not in self._compiled_steps:
                self._compiled_steps[key] = self._make_hetero_step(
                    net, xs.shape[1:], xs.dtype)
            with step_guard("pipeline_step", component="pipeline_master",
                            iteration=net.iteration):
                with self._phases.phase("dispatch"):
                    if res is not None:

                        def dispatch(tree=tree, opt_state=opt_state):
                            return self._compiled_steps[key](
                                tree, opt_state,
                                jnp.asarray(float(net.iteration)), xs, ys)

                        tree, opt_state, loss = res.step(
                            dispatch, net.iteration, net=net)
                    else:
                        tree, opt_state, loss = self._compiled_steps[key](
                            tree, opt_state,
                            jnp.asarray(float(net.iteration)), xs, ys)
            net.score_value = loss
            net.iteration += 1
            self._phases.steps += 1
            notify_listeners(net, len(x))
            if res is not None and res.cm is not None:
                trigger = res.cm.due(net.iteration)
                if trigger is not None:
                    unflatten_back()
                    res.cm.save(net, trigger=trigger)
        unflatten_back()
        if stopped and res is not None:
            res.on_preempt(net)

    # --- facade <-> pipeline param tree conversion (keys: pfx/ blk/ sfx/)
    def _stack_tree(self, per_layer: Dict[str, Any]) -> Dict[str, Any]:
        out = {}
        for l in self._pfx:
            if l.name in per_layer:
                out[f"pfx/{l.name}"] = per_layer[l.name]
        for j in range(len(self._template)):
            trees = [per_layer.get(g[j].name, {}) for g in self._stage_groups]
            if trees[0]:
                out[f"blk/{j}"] = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *trees)
        for l in self._sfx:
            if l.name in per_layer:
                out[f"sfx/{l.name}"] = per_layer[l.name]
        return out

    def _unstack_tree(self, tree: Dict[str, Any]) -> Dict[str, Any]:
        out = {}
        for k, v in tree.items():
            kind, _, tail = k.partition("/")
            if kind == "blk":
                j = int(tail)
                for s, g in enumerate(self._stage_groups):
                    out[g[j].name] = jax.tree_util.tree_map(
                        lambda a: a[s], v)
            else:
                out[tail] = v
        return out

    def _make_compiled_step(self, net, x_mb_shape, x_dtype):
        S = self.n_stages
        M = self.n_microbatches
        mesh = self._mesh
        cfg = self._upd_cfg
        pfx, sfx, template = self._pfx, self._sfx, self._template
        out_layer = sfx[-1]

        def prefix_fwd(tree, a):
            for l in pfx:
                a, _ = l.apply(tree.get(f"pfx/{l.name}", {}), {}, a,
                               train=True, rng=None)
            return a

        def stage_fwd(blk, a):
            for j, l in enumerate(template):
                a, _ = l.apply(blk.get(f"blk/{j}", {}), {}, a,
                               train=True, rng=None)
            return a

        def suffix_loss(tree, a, y):
            for l in sfx[:-1]:
                a, _ = l.apply(tree.get(f"sfx/{l.name}", {}), {}, a,
                               train=True, rng=None)
            return out_layer.score(tree[f"sfx/{out_layer.name}"], a, y)

        # static activation shape: block io shape == prefix output shape
        pfx_tree = {k: v for k, v in self._stack_tree(net.params).items()
                    if k.startswith("pfx/")}
        probe = jax.eval_shape(prefix_fwd, pfx_tree,
                               jax.ShapeDtypeStruct(x_mb_shape, x_dtype))

        def spmd(pfx_p, blk_p, sfx_p, xs, ys):
            idx = lax.axis_index("pipe")
            blk_local = jax.tree_util.tree_map(lambda a: a[0], blk_p)
            perm = [(i, i + 1) for i in range(S - 1)]

            def local_loss(pfx_p, blk_local, sfx_p):
                state0 = jnp.zeros(probe.shape, probe.dtype)
                state0 = lax.pcast(state0, ("pipe",), to="varying")

                def run_tick(state, t):
                    a0 = prefix_fwd(pfx_p, xs[jnp.clip(t, 0, M - 1)])
                    inp = jnp.where(idx == 0, a0, state)
                    outv = stage_fwd(blk_local, inp)
                    m_out = t - (S - 1)
                    l = suffix_loss(sfx_p, outv,
                                    ys[jnp.clip(m_out, 0, M - 1)])
                    return outv, l

                if self.remat:  # O(1) residuals/tick; ppermute stays out
                    run_tick = jax.checkpoint(run_tick)

                def tick(carry, t):
                    state, loss_sum = carry
                    outv, l = run_tick(state, t)
                    m_out = t - (S - 1)
                    loss_sum = loss_sum + jnp.where(
                        (idx == S - 1) & (m_out >= 0), l, 0.0)
                    state = lax.ppermute(outv, "pipe", perm)
                    return (state, loss_sum), None

                loss0 = lax.pcast(jnp.zeros(()), ("pipe",), to="varying")
                (_, loss_sum), _ = lax.scan(
                    tick, (state0, loss0), jnp.arange(M + S - 1))
                # LOCAL loss only (nonzero on the last stage).  Differentiating
                # the psum'd total would double-count: every device's output
                # would back-propagate cotangents into every stage's params.
                return loss_sum / M

            loss, (gp, gb, gs) = jax.value_and_grad(
                local_loss, argnums=(0, 1, 2))(pfx_p, blk_local, sfx_p)
            loss = lax.psum(loss, "pipe")
            gp = lax.psum(gp, "pipe")
            gs = lax.psum(gs, "pipe")
            gb = jax.tree_util.tree_map(lambda a: a[None], gb)
            return loss, gp, gb, gs

        repl, piped = P(), P("pipe")
        sharded = jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(repl, piped, repl, repl, repl),
            out_specs=(repl, repl, piped, repl),
            check_vma=False,
        )
        reg_layers = ([(f"pfx/{l.name}", l) for l in pfx if l.has_params()]
                      + [(f"blk/{j}", l) for j, l in enumerate(template)
                         if l.has_params()]
                      + [(f"sfx/{l.name}", l) for l in sfx if l.has_params()])

        def reg_fn(tree):
            r = jnp.zeros(())
            for key, l in reg_layers:
                if key in tree:
                    r = r + l.reg_score(tree[key])
            return r

        def step(tree, opt_state, it, xs, ys):
            pfx_p = {k: v for k, v in tree.items() if k.startswith("pfx/")}
            blk_p = {k: v for k, v in tree.items() if k.startswith("blk/")}
            sfx_p = {k: v for k, v in tree.items() if k.startswith("sfx/")}
            loss, gp, gb, gs = sharded(pfx_p, blk_p, sfx_p, xs, ys)
            reg_val, reg_g = jax.value_and_grad(reg_fn)(tree)
            grads = {**gp, **gb, **gs}
            grads = jax.tree_util.tree_map(jnp.add, grads,
                                           {k: reg_g[k] for k in grads})
            updates, new_opt = upd.update(cfg, grads, opt_state, it, {},
                                          params={k: tree[k] for k in grads})
            new_tree = {
                k: (upd.apply_updates(v, updates[k]) if k in updates else v)
                for k, v in tree.items()
            }
            return new_tree, new_opt, loss + reg_val

        return instrument(jax.jit(step, donate_argnums=(0, 1)),
                          "PipelineParallelTrainingMaster.compiled_step", argnums=(2, 3, 4))

    def _execute_compiled(self, net, iterator, res=None):
        from deeplearning4j_tpu.resilience import preemption_requested

        M = self.n_microbatches
        tree = self._stack_tree(net.params)
        opt_state = {slot: self._stack_tree(per_layer)
                     for slot, per_layer in net.updater_state.items()}
        place = lambda t: {
            k: jax.device_put(v, self._blk_sharding if k.startswith("blk/")
                              else self._repl_sharding)
            for k, v in t.items()}
        tree = place(tree)
        opt_state = {slot: place(t) for slot, t in opt_state.items()}
        # ledger over the placed trees: blk/ leaves are [S, ...] sharded
        # over 'pipe' (factor 1), pfx/sfx replicated on every stage device
        shardstats.record_ledger(
            "pipeline_master", {"params": tree, "updater_state": opt_state},
            data_axis_size=self.n_stages)

        def unstack_back():
            net.params.update(self._unstack_tree(tree))
            for slot, t in opt_state.items():
                net.updater_state[slot].update(self._unstack_tree(t))

        stopped = False
        for ds in iterator:
            if res is not None and res.skip_batch():
                continue   # auto-resume: batch already covered by the ckpt
            if preemption_requested():
                stopped = True
                break
            if ds.features_mask is not None or ds.labels_mask is not None:
                raise ValueError("pipeline master does not support masked batches")
            x = np.asarray(ds.features)
            y = np.asarray(ds.labels)
            if len(x) % M:
                raise ValueError(f"batch {len(x)} not divisible by "
                                 f"{M} microbatches")
            xs = jnp.asarray(x.reshape((M, len(x) // M) + x.shape[1:]))
            ys = jnp.asarray(y.reshape((M, len(y) // M) + y.shape[1:]))
            key = (xs.shape, ys.shape)  # probe shape is batch-dependent
            if key not in self._compiled_steps:
                self._compiled_steps[key] = self._make_compiled_step(
                    net, xs.shape[1:], xs.dtype)
            with step_guard("pipeline_step", component="pipeline_master",
                            iteration=net.iteration):
                with self._phases.phase("dispatch"):
                    if res is not None:

                        def dispatch(tree=tree, opt_state=opt_state):
                            return self._compiled_steps[key](
                                tree, opt_state,
                                jnp.asarray(float(net.iteration)), xs, ys)

                        tree, opt_state, loss = res.step(
                            dispatch, net.iteration, net=net)
                    else:
                        tree, opt_state, loss = self._compiled_steps[key](
                            tree, opt_state,
                            jnp.asarray(float(net.iteration)), xs, ys)
            net.score_value = loss  # device scalar; fetched lazily on read
            net.iteration += 1
            self._phases.steps += 1
            notify_listeners(net, len(x))
            if res is not None and res.cm is not None:
                trigger = res.cm.due(net.iteration)
                if trigger is not None:
                    # unstacking the whole tree is the fold-back cost; paid
                    # only when a save is actually due
                    unstack_back()
                    res.cm.save(net, trigger=trigger)
        unstack_back()
        if stopped and res is not None:
            res.on_preempt(net)

    # ---------------------------------------------------------------- train
    def execute_training(self, net, iterator):
        from deeplearning4j_tpu.resilience import FitResilience

        res = None
        if self.checkpoint_manager is not None or self.retry_policy is not None:
            res = FitResilience("pipeline_master", self.checkpoint_manager,
                                self.retry_policy, net=net)
        intro_held = None
        if getattr(net.conf, "introspection", None) is not None:
            # the pipeline master splits updater state per stage by LAYER
            # name; the layerless __introspect__ subtree cannot shard that
            # way, so introspection does not cover this master yet — park
            # the subtree for the duration of the fit instead of feeding
            # it into the per-stage split (docs/observability.md)
            from deeplearning4j_tpu.observability import introspection

            intro_held = net.updater_state.pop(introspection.STATE_KEY, None)
        num_held = None
        if getattr(net.conf, "numerics", None) is not None:
            # the layerless __numerics__ precision-ledger subtree is
            # parked for the same reason — stale over a pipeline fit
            from deeplearning4j_tpu.observability import numerics

            num_held = net.updater_state.pop(numerics.STATE_KEY, None)
        try:
            return self._execute_with_master(net, iterator, res)
        finally:
            if intro_held is not None:
                net.updater_state[introspection.STATE_KEY] = intro_held
            if num_held is not None:
                net.updater_state[numerics.STATE_KEY] = num_held

    def _execute_with_master(self, net, iterator, res):
        from deeplearning4j_tpu.resilience import preemption_requested

        if not self._built:
            self._build(net)
        if self._mode == "compiled":
            if self._compiled_kind == "hetero":
                return self._execute_hetero(net, iterator, res)
            return self._execute_compiled(net, iterator, res)
        S = len(self.stages)
        # place each stage's params + updater state on its device
        stage_params = [
            jax.device_put(self._stage_params(net, s), self.devices[s])
            for s in range(S)
        ]
        stage_upd = [
            jax.device_put(
                {slot: {n: tree[n] for n in stage_params[s] if n in tree}
                 for slot, tree in net.updater_state.items()},
                self.devices[s])
            for s in range(S)
        ]
        # per-STAGE sharding ledger: each stage's rows sum to the
        # single-device totals (the memory win pipeline placement buys)
        shardstats.record_ledger("pipeline_master", {
            **{f"params_stage{s}": stage_params[s] for s in range(S)},
            **{f"updater_state_stage{s}": stage_upd[s] for s in range(S)},
        })

        if self._workers is None:
            self._workers = WorkerTelemetry("pipeline_master")
        for ds in iterator:
            if res is not None and res.skip_batch():
                continue   # auto-resume: batch already covered by the ckpt
            if preemption_requested():
                self._merge_back(net, stage_params, stage_upd)
                if res is not None:
                    res.on_preempt(net)
                return
            with step_guard("pipeline_step", component="pipeline_master",
                            iteration=net.iteration):
                if res is not None:
                    loss = res.step(
                        lambda: self._train_batch(net, ds, stage_params,
                                                  stage_upd),
                        net.iteration, net=net)
                else:
                    loss = self._train_batch(net, ds, stage_params, stage_upd)
            net.score_value = loss  # device scalar; fetched lazily on read
            net.iteration += 1
            self._phases.steps += 1
            notify_listeners(net, len(ds))
            if res is not None and res.cm is not None:
                trigger = res.cm.due(net.iteration)
                if trigger is not None:
                    self._merge_back(net, stage_params, stage_upd)
                    res.cm.save(net, trigger=trigger)
        self._merge_back(net, stage_params, stage_upd)

    def _merge_back(self, net, stage_params, stage_upd) -> None:
        """Merge per-stage params/updater state back into the facade (loop
        end, due checkpoint saves, preemption stop)."""
        S = len(self.stages)
        for s in range(S):
            for name, p in stage_params[s].items():
                net.params[name] = jax.device_put(p, self.devices[0])
        for slot in net.updater_state:
            merged = {}
            for s in range(S):
                merged.update(stage_upd[s][slot])
            net.updater_state[slot] = {
                n: jax.device_put(v, self.devices[0])
                for n, v in merged.items()}

    def _train_batch(self, net, ds, stage_params, stage_upd):
        if ds.features_mask is not None or ds.labels_mask is not None:
            raise ValueError("pipeline master does not support masked batches")
        phase_t0 = self._phases.totals()
        S = len(self.stages)
        M = self.n_microbatches
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        if len(x) % M:
            raise ValueError(f"batch {len(x)} not divisible by "
                             f"{M} microbatches")
        xs = jnp.split(x, M)
        ys = jnp.split(y, M)

        pullbacks = [[None] * S for _ in range(M)]
        losses = [None] * M
        grads = [None] * S

        def forward(m):
            # async dispatch overlaps (m, s) with (m+1, s-1); the per-stage
            # timers measure host DISPATCH time per stage (device compute is
            # async), which is what serializes the orchestrated schedule
            a = jax.device_put(xs[m], self.devices[0])
            for s in range(S - 1):
                with self._phases.phase(f"stage{s}_fwd"):
                    a, vjp = jax.vjp(self._stage_fwds[s], stage_params[s], a)
                pullbacks[m][s] = vjp
                a = jax.device_put(a, self.devices[s + 1])
            y_m = jax.device_put(ys[m], self.devices[S - 1])
            with self._phases.phase(f"stage{S - 1}_fwd"):
                loss_m, vjp = jax.vjp(self._last_stage, stage_params[S - 1],
                                      a, y_m)
            pullbacks[m][S - 1] = vjp
            losses[m] = loss_m

        def backward(m):
            seed = jnp.ones((), losses[m].dtype) / M
            with self._phases.phase(f"stage{S - 1}_bwd"):
                gp, ga, _gy = pullbacks[m][S - 1](seed)
            grads[S - 1] = gp if grads[S - 1] is None else jax.tree_util.tree_map(
                jnp.add, grads[S - 1], gp)
            for s in range(S - 2, -1, -1):
                ga = jax.device_put(ga, self.devices[s])
                with self._phases.phase(f"stage{s}_bwd"):
                    gp, ga = pullbacks[m][s](ga)
                grads[s] = gp if grads[s] is None else jax.tree_util.tree_map(
                    jnp.add, grads[s], gp)
            pullbacks[m] = [None] * S   # release stashed activations

        if self.schedule == "1f1b":
            # PipeDream-flush: after the S-1-tick fill, each microbatch's
            # backward follows its forward — at most S pullbacks live at
            # once (vs M for GPipe), same (S-1)/(M+S-1) bubble
            for t in range(M + S - 1):
                if t < M:
                    forward(t)
                if t - (S - 1) >= 0:
                    backward(t - (S - 1))
        else:
            # GPipe: all forwards (fill), then all backwards (drain)
            for m in range(M):
                forward(m)
            for m in range(M):
                backward(m)

        # regularization value+gradients + updater apply, per stage on-device
        it = jnp.asarray(float(net.iteration))
        reg_vals = []
        for s in range(S):
            reg_val, reg_grad = self._reg_fns[s](stage_params[s])
            reg_vals.append(reg_val)  # no host sync inside the dispatch loop
            g = jax.tree_util.tree_map(jnp.add, grads[s], reg_grad)
            updates, stage_upd[s] = upd.update(
                self._upd_cfg, g, stage_upd[s], it, self._lr_overrides,
                params=stage_params[s])
            stage_params[s] = {
                ln: (upd.apply_updates(stage_params[s][ln], u)
                     if (u := updates.get(ln)) else stage_params[s][ln])
                for ln in stage_params[s]
            }
        # per-stage dispatch time this batch (phase-total deltas) -> the
        # worker families + straggler detector; an unbalanced stage split
        # shows up as worker "stage<s>" straggling
        if self._workers is not None:
            t1 = self._phases.totals()
            for s in range(S):
                fwd = (t1.get(f"stage{s}_fwd", 0.0)
                       - phase_t0.get(f"stage{s}_fwd", 0.0))
                bwd = (t1.get(f"stage{s}_bwd", 0.0)
                       - phase_t0.get(f"stage{s}_bwd", 0.0))
                self._workers.observe(f"stage{s}", fwd + bwd,
                                      phases={"fwd": fwd, "bwd": bwd})

        # score matches serial _loss_fn: data loss + regularization penalty
        return (sum(jax.device_get(l) for l in losses) / M
                + sum(float(r) for r in reg_vals))
