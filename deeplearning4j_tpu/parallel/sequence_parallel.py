"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO sequence parallelism — long sequences are handled by
truncated BPTT and masking only (SURVEY.md §5; reference
``MultiLayerNetwork.java:1176``).  For the TPU framework long context is
first-class: the sequence axis is a mesh axis (``backend.AXIS_SEQ``), each
chip holds a contiguous time shard, and attention runs either as

- **ring attention** (`ring_attention`): K/V blocks rotate around the ring
  of sequence shards via ``lax.ppermute`` while each chip folds one block
  per step into an online-softmax accumulator (blockwise/flash-style
  numerically stable rescaling).  Communication is neighbor-only, so it
  rides ICI at O(T/P) memory per chip — never materializing the [T, T]
  score matrix or an all-gathered K/V.
- **Ulysses attention** (`ulysses_attention`): two ``lax.all_to_all``s
  reshard [B, T/P, H, D] -> [B, T, H/P, D], run exact local attention per
  head group, and reshard back.  Cheaper for moderate T with many heads.

``SequenceParallelTrainingMaster`` jits a FULL training step under
``shard_map`` over (data, seq): batch sharded over 'data', time sharded over
'seq', params replicated, gradients pmean'd over both axes.  Equivalence to
single-device training is the correctness contract (tests mirror the
reference's distributed-vs-local pattern,
``TestCompareParameterAveragingSparkVsSingleMachine``).
"""

from __future__ import annotations

import collections
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.backend import device as backend
from deeplearning4j_tpu.optimize import updaters as upd

_NEG = -1e30


def ring_attention(q, k, v, mask=None, *, axis_name: str,
                   causal: bool = False, window: Optional[int] = None):
    """Blockwise ring attention over one mesh axis.

    Must be called inside ``shard_map``; ``q/k/v`` are local sequence shards
    of shape [B, T_local, H, D] (shard i holds global timesteps
    ``[i*T_local, (i+1)*T_local)``); ``mask`` is the local [B, T_local]
    key-padding shard and rotates around the ring with K/V.  Returns the
    local shard of the exact attention output — numerically identical (up to
    fp associativity) to full attention on the gathered sequence.
    """
    from deeplearning4j_tpu.nn.layers.attention import check_window

    check_window(causal, window)
    n_shards = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    hkv = k.shape[2]
    grouped = hkv != h
    if grouped and h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    groups = h // hkv
    q_off = idx * t_local
    qpos = q_off + jnp.arange(t_local)

    # online-softmax accumulators in >=f32; pcast marks them as varying
    # over the ring axis so the scan carry typechecks under shard_map
    acc = jnp.promote_types(q.dtype, jnp.float32)
    qf = q.astype(acc)
    o0 = lax.pcast(jnp.zeros((b, h, t_local, d), acc), (axis_name,), to="varying")
    l0 = lax.pcast(jnp.zeros((b, h, t_local), acc), (axis_name,), to="varying")
    m0 = lax.pcast(jnp.full((b, h, t_local), _NEG, acc), (axis_name,), to="varying")
    scale = jnp.asarray(1.0 / np.sqrt(d), acc)
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def fold_block(o, l, m, k_cur, v_cur, mask_cur, s):
        """Online-softmax fold of the K/V block currently held (block s of
        the rotation; globally it is shard (idx - s) mod n_shards)."""
        src = (idx - s) % n_shards
        kpos = src * t_local + jnp.arange(t_local)
        if grouped:
            # GQA: contract each KV head against its query-head group
            # directly — the rotating K/V stays at H_kv heads, so ICI
            # traffic and per-chip K/V memory keep the GQA shrink.
            # (hkv, g) flattens in the same head order as jnp.repeat.
            qg = qf.reshape(b, t_local, hkv, groups, d)
            scores = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qg, k_cur.astype(acc)
            ).reshape(b, h, t_local, t_local) * scale
        else:
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", qf, k_cur.astype(acc)) * scale
        if causal:
            blk_mask = qpos[:, None] >= kpos[None, :]       # [Tq, Tk]
            if window is not None:
                # sliding window by GLOBAL position, same band as the
                # local paths: kpos in [qpos - window + 1, qpos]
                blk_mask &= kpos[None, :] > qpos[:, None] - window
            valid = blk_mask[None, None]
        else:
            valid = jnp.ones((1, 1, t_local, t_local), bool)
        if mask_cur is not None:
            valid = valid & mask_cur.astype(bool)[:, None, None, :]
        scores = jnp.where(valid, scores, _NEG)
        m_blk = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(scores - m_new[..., None]), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        if grouped:
            pg = p.reshape(b, hkv, groups, t_local, t_local)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", pg, v_cur.astype(acc)
                            ).reshape(b, h, t_local, d)
        else:
            pv = jnp.einsum("bhqk,bkhd->bhqd", p, v_cur.astype(acc))
        o = o * alpha[..., None] + pv
        return o, l, m_new

    # step 0 folds the local block with no communication; remaining steps
    # rotate FIRST then fold, so no ppermute result is ever discarded
    o, l, m = fold_block(o0, l0, m0, k, v, mask, 0)

    def body(carry, s):
        o, l, m, k_cur, v_cur, mask_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        if mask_cur is not None:
            mask_cur = lax.ppermute(mask_cur, axis_name, perm)
        o, l, m = fold_block(o, l, m, k_cur, v_cur, mask_cur, s)
        return (o, l, m, k_cur, v_cur, mask_cur), None

    if n_shards > 1:
        (o, l, m, _, _, _), _ = lax.scan(
            body, (o, l, m, k, v, mask), jnp.arange(1, n_shards))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)   # [B,T,H,D]


def ulysses_attention(q, k, v, *, axis_name: str, causal: bool = False,
                      window: Optional[int] = None):
    """DeepSpeed-Ulysses-style all-to-all sequence parallelism.

    Inside ``shard_map``: reshard time-sharded heads to head-sharded full
    sequence, run exact local attention, reshard back.  Requires
    ``H % n_shards == 0``.
    """
    from deeplearning4j_tpu.helpers import get_helper
    from deeplearning4j_tpu.nn.layers.attention import (
        check_window, dot_product_attention,
    )

    check_window(causal, window)
    n_shards = lax.psum(1, axis_name)

    def to_heads(x):   # [B, T/P, H, D] -> [B, T, H/P, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    # after resharding the local attention is exact full-T — route it
    # through the flash helper seam as SelfAttentionLayer does; the helper
    # owns the policy (under shard_map only the compiled path qualifies)
    helper = get_helper("attention")
    # flash helper is MHA-only (its to_bh reshape assumes k/v share q's
    # head count) — GQA (H_kv < H) must take the grouped einsum path
    if (helper is not None and qh.dtype != jnp.float64
            and kh.shape[2] == qh.shape[2]
            and helper.supports(qh.shape[1], qh.shape[3],
                                under_shard_map=True)):
        o = helper.attend(qh, kh, vh, causal=causal, window=window)
    else:
        o = dot_product_attention(qh, kh, vh, causal=causal, window=window)
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ring_self_attention(q, k, v, mesh: Optional[Mesh] = None, *,
                        causal: bool = False, window: Optional[int] = None,
                        impl: str = "ring",
                        seq_axis: str = backend.AXIS_SEQ):
    """Convenience wrapper: global [B, T, H, D] arrays in, attention over a
    sequence-sharded mesh, global-layout result out (still sharded)."""
    mesh = mesh or backend.default_mesh()
    fn = ring_attention if impl == "ring" else ulysses_attention
    spec = P(None, seq_axis)
    return jax.shard_map(
        functools.partial(fn, axis_name=seq_axis, causal=causal,
                          window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)


class SequenceParallelTrainingMaster:
    """Train with batch sharded over 'data' AND time sharded over 'seq'.

    Supported nets: Sequential stacks whose layers are timestep-local
    (Embedding/Dense/LayerNorm/Activation/RnnOutput) plus
    ``SelfAttentionLayer(seq_axis='seq')`` — i.e. transformer LMs.  Recurrent
    scan layers (LSTM) carry state across time shards and are NOT supported
    here; use TBPTT for those (reference parity path).

    The whole step is ONE ``shard_map``-ped XLA program: local forward/
    backward on [B/Kd, T/Ks] shards, ring collectives inside attention,
    one pmean of loss+grads over (data, seq) — no host round-trips.
    """

    def __init__(self, mesh: Optional[Mesh] = None, collect_stats: bool = False):
        self.mesh = mesh or backend.default_mesh()
        self.collect_stats = collect_stats
        # bounded window (last 1024): O(1) memory over long runs
        self._stats: Dict[str, Any] = {
            "steps": 0, "step_time_ms": collections.deque(maxlen=1024)}
        self._step = None

    def _build(self, net):
        cfg = net.conf.updater
        lr_overrides = {
            l.name: l.learning_rate for l in net.layers if l.learning_rate is not None
        }
        mesh = self.mesh
        axes = (backend.AXIS_DATA, backend.AXIS_SEQ)
        repl = P()
        data_seq = P(backend.AXIS_DATA, backend.AXIS_SEQ)

        ks = mesh.shape[backend.AXIS_SEQ]
        reg_layers = [l for l in net.layers if l.has_params()]

        def local_loss(params, net_state, x, y, rng):
            """Loss convention (reference, losses.score): per-example SUM over
            time, MEAN over batch.  Each seq shard's data term is a partial
            time-sum -> psum over 'seq' reassembles it; the replicated reg
            term must count ONCE, so scale it to reg/ks before the psum."""
            full, aux = net._loss_fn(params, net_state, x, y, rng)
            reg = jnp.zeros(())
            for l in reg_layers:
                reg = reg + l.reg_score(params[l.name])
            return full - reg * (1.0 - 1.0 / ks), aux

        def step(params, upd_state, net_state, iteration, x, y, rng):
            # distinct dropout streams per shard
            rng = jax.random.fold_in(rng, lax.axis_index(backend.AXIS_DATA))
            rng = jax.random.fold_in(rng, lax.axis_index(backend.AXIS_SEQ))
            (loss, (new_ns, _)), grads = jax.value_and_grad(
                local_loss, has_aux=True)(params, net_state, x, y, rng)
            # time-sum across seq shards, example-mean across data shards
            loss = lax.pmean(lax.psum(loss, backend.AXIS_SEQ), backend.AXIS_DATA)
            grads = {k2: v for k2, v in grads.items() if v}
            grads = lax.pmean(lax.psum(grads, backend.AXIS_SEQ), backend.AXIS_DATA)
            new_ns = lax.pmean(new_ns, axes) if new_ns else new_ns
            updates, new_us = upd.update(cfg, grads, upd_state, iteration,
                                         lr_overrides, params=params)
            new_params = {
                ln: (upd.apply_updates(params[ln], u)
                     if (u := updates.get(ln)) else params[ln])
                for ln in params
            }
            return new_params, new_us, new_ns, loss

        sharded = jax.shard_map(
            step, mesh=mesh,
            in_specs=(repl, repl, repl, repl, data_seq, data_seq, repl),
            out_specs=(repl, repl, repl, repl),
            check_vma=False,
        )
        self._step = jax.jit(sharded, donate_argnums=(0, 1, 2))
        self._data_sharding = NamedSharding(mesh, data_seq)
        self._repl_sharding = NamedSharding(mesh, repl)

    def execute_training(self, net, iterator):
        import time

        if self._step is None:
            self._build(net)
        params = jax.device_put(net.params, self._repl_sharding)
        upd_state = jax.device_put(net.updater_state, self._repl_sharding)
        ns = jax.device_put(net.net_state, self._repl_sharding)
        kd = self.mesh.shape[backend.AXIS_DATA]
        ks = self.mesh.shape[backend.AXIS_SEQ]
        for ds in iterator:
            # dl4jlint: disable-next-line=host-sync-in-hot-path -- iterator yields host numpy; asarray is a view, the device transfer is the explicit device_put below
            x, y = np.asarray(ds.features), np.asarray(ds.labels)
            if x.shape[0] % kd or x.shape[1] % ks:
                raise ValueError(
                    f"batch {x.shape[0]} / time {x.shape[1]} must divide mesh "
                    f"(data={kd}, seq={ks})")
            t0 = time.perf_counter()
            xj = jax.device_put(jnp.asarray(x), self._data_sharding)
            yj = jax.device_put(jnp.asarray(y), self._data_sharding)
            params, upd_state, ns, loss = self._step(
                params, upd_state, ns, jnp.asarray(float(net.iteration)),
                xj, yj, net._keys.next())
            net.score_value = loss  # device scalar; fetched lazily on read
            net.iteration += 1
            if self.collect_stats:
                self._stats["step_time_ms"].append((time.perf_counter() - t0) * 1e3)
            self._stats["steps"] += 1
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration)
        net.params, net.updater_state, net.net_state = params, upd_state, ns

    def training_stats(self):
        out = dict(self._stats)
        out["step_time_ms"] = list(out["step_time_ms"])
        return out
