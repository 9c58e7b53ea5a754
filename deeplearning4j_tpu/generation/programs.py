"""Compiled generation programs: bucketed prefill + ONE decode step.

The whole engine dispatches exactly ``len(prefill_buckets) + 3`` XLA
programs per model version, all AOT-warmed before the version serves:

- ``prefill_<bucket>``: one request's (non-shared) prompt suffix, padded
  up to the bucket length, forwarded through the paged carries in a
  single [1, bucket] call — writes its cache rows (K/V, or latent rows)
  into the request's pages, samples the first token from the last
  REAL prompt position's logits, and puts it at the request's lane of
  the ids vector it was handed (below).
- ``decode``: one token for EVERY slot in a single [slots, 1] call —
  the iteration-level batch.  Idle slots ride along pointed at the
  trash page with temperature 0 (and whatever id their last request
  left); their lanes are pure garbage-in/garbage-out and the scheduler
  ignores their outputs.
- ``read_page`` / ``write_page``: one page's slice out of / into every
  pool — the prefix cache's host-tier transport.

THE IDS VECTOR ``[slots]`` lives on the device: a decode step's sampled
ids are the next step's ``tokens`` as they are, and a prefill writes its
first token into the vector at its lane, so the engine dispatches the
next program without having seen a value (``generation/engine.py``).

A POOL is whatever page-major arrays a layer's ``init_paged_cache``
returns: ``pk``/``pv`` [P, Hkv, page, D] for ``SelfAttentionLayer``
(``wk``/``wv`` [Pw, Hkv, page, D], the WINDOW kind, where it has a
``window``), one latent ``pc`` [P, page, W] for ``LatentAttentionLayer``,
and the STATE kind, ``sh``/``sc`` [slots + 1, ...] — one row a slot, not
pages — for a layer that carries recurrent state (``MambaLayer``,
``GravesLSTM``).  Every function here reaches them through one walker,
``map_pools``.  Under window layers the dispatch's block table is two tables
side by side (``PagedKVCache.table_width``): ``_attach`` hands a window pool
the ring columns, every other paged pool the global ones; a state pool gets
no table but a prefill's SLOT ROW (``slot + 1``) or, in the decode step,
whose lane ``i`` owns row ``i + 1``, the mask of the lanes that run a
request (an idle lane moves no state).  The page transport (``read_page`` /
``write_page`` / ``page_nbytes``) SKIPS the state kind: a page is a span of
positions, a state row is a whole prefix, and no page boundary has a state
of its own (``docs/serving.md``, "State slots beside pages").

Shapes are closed by construction (slot count, pool size, block-table
width, bucket lengths are all fixed at engine construction), so steady
state compiles exactly nothing — proven through the version's
``RecompileDetector`` the same way the PR-2 serving warmup proves it.

Pools are donated on every call: XLA writes the new rows in place
instead of copying pool-sized buffers per token.

Parameters reach ``prefill_<bucket>`` and ``decode`` as the version's
SERVING SNAPSHOT (``serving_params``): the net's tree with every floating
leaf cast to ``conf.compute_dtype`` once, by one jitted program, instead
of inside each execution.  The programs are compiled for that tree.
"""

from __future__ import annotations

import logging
import operator
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.generation.paged_cache import TRASH_PAGE
from deeplearning4j_tpu.helpers.paged_attention import pool_kind
from deeplearning4j_tpu.models.common import cast_to_compute
from deeplearning4j_tpu.models.decode import (
    _cg_single_io, _ids_need_time_axis, _last_logits_fwd,
)
from deeplearning4j_tpu.nn.layers.base import ServingCall
from deeplearning4j_tpu.nn.layers.composite import gauging
from deeplearning4j_tpu.nn.layers.moe import counting
from deeplearning4j_tpu.utils.sampling import _resolve_encoding, sample_tokens


logger = logging.getLogger("deeplearning4j_tpu.generation")

# the snapshot's one program; versions of one architecture share its
# compiled form, so a deploy or a re-cast compiles nothing
_cast = jax.jit(cast_to_compute, static_argnums=(1,))


def named_layers_of(net) -> List[Tuple[str, object]]:
    """(name, layer) pairs for either facade — the walk
    ``models.decode.generate`` uses, shared here for pool seeding."""
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork

    if isinstance(net, MultiLayerNetwork):
        return [(l.name, l) for l in net.layers]
    _cg_single_io(net)   # generation feeds back ONE token stream
    return [(n, net.nodes[n].layer) for n in net.topo
            if net.nodes[n].layer is not None]


def _layers_where(net, wanted):
    """Every layer of ``net`` that ``wanted(layer)`` holds of, those inside
    composite layers too."""

    def walk(layer):
        if wanted(layer):
            yield layer
        for sub in getattr(layer, "layers", ()):
            yield from walk(sub)

    return [a for _, l in named_layers_of(net) for a in walk(l)]


def window_ring_pages(net, page_size: int) -> int:
    """Pages a slot that ``net``'s window layers ring through (their
    ``paged_ring``; the widest, since one manager's page ids address every
    window pool), 0 for a net with none."""
    return max((l.paged_ring(page_size) or 0 for l in _layers_where(
        net, lambda l: hasattr(l, "paged_ring"))), default=0)


def has_state_pools(net) -> bool:
    """Whether a layer of ``net`` keeps its streaming state in STATE SLOTS
    (``holds_state_slots`` on its class: one row a slot, begun anew at
    admission)."""
    return bool(_layers_where(
        net, lambda l: getattr(l, "holds_state_slots", False)))


def window_pool_pages(slots: int, ring: int) -> int:
    """Pages of the window kind's pools: every slot's ring and the trash
    page; 0 for a net without window layers."""
    return slots * ring + 1 if ring else 0


def seed_paged_pools(net, num_pages: int, page_size: int,
                     dtype=None, window_pages: Optional[int] = None,
                     state_slots: Optional[int] = None) -> Dict:
    """The pools of every layer of ``net`` that keeps state while streaming
    (the paged analog of ``models.common.seed_stream_caches``).  Raises on a
    layer whose carry the engine cannot address — the engine must fail at
    set-up, not serve wrong tokens.  ``window_pages`` sizes the pools of
    window layers, ``state_slots`` those of state layers."""
    cache_dtype = (jnp.dtype(dtype) if dtype else jnp.float32)
    pools = {}
    for name, layer in named_layers_of(net):
        if hasattr(layer, "init_paged_cache"):
            c = layer.init_paged_cache(num_pages, page_size, cache_dtype,
                                       window_pages=window_pages,
                                       state_slots=state_slots)
            if c is not None:
                pools[name] = c
        elif hasattr(layer, "apply_with_carry"):
            raise ValueError(
                f"layer '{name}' ({type(layer).__name__}) takes a carry "
                "(apply_with_carry) but has no init_paged_cache; the "
                "generation engine serves a layer that keeps state only "
                "through pools it can address: pages by a block table, or "
                "state slots by a request's slot")
    if not pools:
        raise ValueError(
            "no layer with a paged cache or state slots found — the "
            "generation engine needs at least one causal attention or "
            "recurrent layer with init_paged_cache")
    return pools


def map_pools(fn, pools, *others):
    """The one walker over a pool tree: ``fn(pool, *other)`` at every POOL
    — the dict of page-major arrays ``[num_pages, ...]`` that one layer's
    ``init_paged_cache`` returned (``pk``/``pv`` for ``SelfAttentionLayer``,
    one latent ``pc`` for ``LatentAttentionLayer``; a dict none of whose
    values is a dict) — with the entries at the same path of every tree in
    ``others``; returns the tree of the results.  ``others`` may hold
    more than ``pools`` does (the forward's carries); only ``pools``' paths
    are visited."""
    def walk(c, *o):
        if not any(isinstance(v, dict) for v in c.values()):
            return fn(c, *o)
        return {k: walk(v, *(x[k] for x in o)) for k, v in c.items()}
    return {k: walk(v, *(x[k] for x in others)) for k, v in pools.items()}


def _attach(pools, block, pos, maxp=None, live=None, rows=None, lanes=None):
    """Insert the dispatch's block table / positions beside every pool's
    arrays (the pool pytree holds the arrays alone between dispatches).
    ``maxp`` (a net with window layers): ``block`` holds the global table in
    its first ``maxp`` columns and the ring table after them, and each pool
    gets the one that addresses it; a window pool (``pool_kind``) also gets
    ``live`` [B], the chunk's real tokens, where the dispatch has padding.
    A net with state layers: a state pool gets, in place of a table, the
    dispatch's slot ``rows`` [B] (a prefill's one row, ``slot + 1``) or its
    ``lanes`` [B] bool (the decode step, whose lane ``i`` owns row ``i + 1``:
    True where the lane runs a request), with ``pos`` and ``live``."""
    if maxp is None and rows is None and lanes is None:
        return map_pools(lambda c: {**c, "block": block, "pos": pos}, pools)
    tables = (block, block) if maxp is None else (block[:, :maxp],
                                                  block[:, maxp:])

    def attach(c):
        kind = pool_kind(c)
        if kind == "state":
            out = {**c, "pos": pos, **({"rows": rows} if lanes is None
                                       else {"lanes": lanes})}
        else:
            out = {**c, "block": tables[kind == "window"], "pos": pos}
        if kind != "global" and live is not None:
            out["live"] = live
        return out

    return map_pools(attach, pools)


def _overlay(whole, part):
    """``whole`` with ``part``'s entries in place of its own."""
    return {k: ((_overlay(v, part[k]) if isinstance(v, dict) else part[k])
                if k in part else v) for k, v in whole.items()}


def _paged_only(pools):
    """``pools`` without the state kind: what the page transport moves."""
    def prune(c):
        if not any(isinstance(v, dict) for v in c.values()):
            return {} if pool_kind(c) == "state" else c
        return {k: p for k, v in c.items() if (p := prune(v))}
    return prune(pools)


def _strip(carries, pools):
    """Keep only the updated pools out of the forward's new carries.
    The forward returns a carry entry for EVERY carry-capable layer —
    ``None`` for the ones that ran carry-less (MLP residual blocks) —
    and beside each pool's arrays the block table and positions; what
    goes back has exactly ``pools``' structure, or every warmed program
    would retrace on its first live call."""
    return map_pools(lambda c, new: {k: new[k] for k in c}, pools, carries)


def _with_counts(tokens, counts, gauges=()):
    """What a compute program returns beside the pools: the sampled ids,
    and for a net with expert layers ``(ids, counts)``, the layers'
    ``nn.layers.moe.counting`` vectors summed (one more small array in the
    same fetch).  A net with hyper-connection blocks returns ``(ids, counts,
    gauge)``: the largest of the blocks' ``nn.layers.composite.gauging``
    scalars (``counts`` one zero where it has no expert layer)."""
    if not counts and not gauges:
        return tokens
    total = (sum(counts[1:], counts[0]) if counts
             else jnp.zeros((1,), jnp.int32))
    if not gauges:
        return tokens, total
    return tokens, total, jnp.max(jnp.stack(gauges))


def sampled_ids(sampled):
    """The ids out of what a compute program sampled (``_with_counts``),
    on the device or harvested."""
    return sampled[0] if isinstance(sampled, tuple) else sampled


class GenerationPrograms:
    """The jitted program set for ONE model version (the engine builds a
    fresh set per deploy and AOT-warms it before the version serves)."""

    def __init__(self, net, *, slots: int, pages_per_slot: int,
                 page_size: int, num_pages: int,
                 prefill_buckets: Tuple[int, ...], detector=None):
        self.net = net
        self.slots = int(slots)
        self.pages_per_slot = int(pages_per_slot)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.prefill_buckets = tuple(sorted(int(b) for b in prefill_buckets))
        self.detector = detector
        probe = np.zeros((1, 1), np.int64)
        _, self.one_hot, self.vocab_size = _resolve_encoding(
            net, probe, None, None)
        self.expand_ids = _ids_need_time_axis(net, self.one_hot)
        self._fwd = _last_logits_fwd(net)
        # pages by layer kind: the ring a slot holds of window pages, and
        # that kind's pool; 0 and 0 for a net without window layers
        self.ring = window_ring_pages(net, self.page_size)
        self.num_window_pages = window_pool_pages(self.slots, self.ring)
        # state slots: whether a layer keeps one row of state a slot
        self.state = has_state_pools(net)
        # the dtype the layers are traced in: the stored one where no
        # compute dtype is set (a float64 net keeps the gather)
        dtype = jnp.dtype(net.conf.compute_dtype or next(
            (l.dtype for l in jax.tree_util.tree_leaves(net.params)
             if jnp.issubdtype(l.dtype, jnp.floating)), jnp.float32))
        # each compute program as its layers see it, by ("decode" / bucket,
        # whether every row starts at position 0: the branch a latent
        # layer's prefill takes on the device)
        shapes = {**{b: (1, b) for b in self.prefill_buckets},
                  "decode": (self.slots, 1)}
        self.calls = {
            (name, zero): ServingCall(batch, t, zero, self.page_size,
                                      self.pages_per_slot, self.ring,
                                      self.slots, dtype)
            for name, (batch, t) in shapes.items() for zero in (False, True)}
        # the path each layer takes in each program (``Layer.serving_path``,
        # the rule it branches on when the program is traced): its distinct
        # (kind, path) pairs, by the same keys
        self._layers = _layers_where(net, lambda l: True)
        self.paths = {
            key: tuple(sorted({(l.kind, p) for l in self._layers
                               if (p := l.serving_path(call)) is not None}))
            for key, call in self.calls.items()}
        # validate eagerly (raises on a carry no pool can hold)
        jax.eval_shape(lambda: seed_paged_pools(
            net, 2, page_size, net.conf.compute_dtype, window_pages=2,
            state_slots=1))
        self._decode = jax.jit(self._make_decode(), donate_argnums=(2,))
        self._prefill = {
            b: jax.jit(self._make_prefill(b), donate_argnums=(2,))
            for b in self.prefill_buckets}
        # page transport (prefix-cache host tier): one page's slice
        # out of / into every pool.  Fixed shapes — two more members of
        # the closed program set, warmed with the rest.
        self._read_page = jax.jit(self._make_read_page())
        self._write_page = jax.jit(self._make_write_page(),
                                   donate_argnums=(0,))
        # the serving snapshot: (leaves it was cast from, the cast tree),
        # replaced whole so a reader never sees a mix; None until the
        # first serving_params()
        self._serving = (None, None)
        self.param_casts = 0            # snapshots cast so far

    # ------------------------------------------------------ serving snapshot
    def serving_params(self):
        """The net's parameters as the compute programs take them: every
        floating leaf in ``conf.compute_dtype``, cast ONCE by a single
        jitted program and kept until the net's tree changes.  With
        ``compute_dtype`` None, and for a leaf already in that dtype, the
        snapshot holds the net's own buffer (no copy).

        Never stale: the snapshot remembers the leaves it was cast from,
        and a tree whose leaves are no longer those (``fit`` or
        ``set_params_vector`` rebound ``net.params``, a pretrain loop
        replaced a subtree in place) is cast again here, before the
        dispatch, at the same shapes and dtypes, so nothing recompiles.
        The check is one flatten and an identity comparison per leaf: host
        work only, no device access."""
        leaves, treedef = jax.tree_util.tree_flatten(self.net.params)
        src, snapshot = self._serving
        if (src is not None and len(leaves) == len(src)
                and all(map(operator.is_, leaves, src))):
            return snapshot
        dt = self.net.conf.compute_dtype
        # only the leaves the rule changes go through the program: a jit
        # output is a new buffer even where the function is the identity
        want = jax.eval_shape(lambda t: cast_to_compute(t, dt), leaves)
        todo = [i for i, (a, w) in enumerate(zip(leaves, want))
                if hasattr(a, "dtype") and a.dtype != w.dtype]
        out = list(leaves)
        if todo:
            for i, c in zip(todo, _cast([leaves[i] for i in todo], dt)):
                out[i] = c
        snapshot = jax.tree_util.tree_unflatten(treedef, out)
        self._serving = (leaves, snapshot)
        self.param_casts += bool(todo)    # leaves all in the dtype: no cast
        return snapshot

    # ---------------------------------------------------------------- build
    def fresh_ids(self):
        """The ids vector [slots] before any program has written it, on
        the device like every one after it."""
        return jax.device_put(np.zeros(self.slots, np.int32))

    def fresh_pools(self):
        return seed_paged_pools(self.net, self.num_pages, self.page_size,
                                self.net.conf.compute_dtype,
                                window_pages=self.num_window_pages,
                                state_slots=self.slots)

    def bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if length <= b:
                return b
        raise ValueError(
            f"prompt suffix of {length} tokens exceeds the largest "
            f"prefill bucket {self.prefill_buckets[-1]}")

    def _encode(self, tokens):
        if self.one_hot:
            return jax.nn.one_hot(tokens, self.vocab_size,
                                  dtype=jnp.float32)
        return tokens[..., None] if self.expand_ids else tokens

    def _make_decode(self):
        fwd, encode = self._fwd, self._encode
        maxp = self.pages_per_slot if self.ring else None
        state = self.state

        def decode_step(params, net_state, pools, block, pos, tokens,
                        keys, token_idx, temps, top_ks, top_ps):
            """One token for every slot: [S] in, [S] out.  ``tokens`` is
            what the last step or prefill left in each lane."""
            x = encode(tokens[:, None])

            def real():      # an idle slot's table points at the trash page
                return block[:, :1] != TRASH_PAGE

            # a lane's state row is its slot's; an idle lane moves none
            lanes = real()[:, 0] if state else None
            with counting(real) as counts, gauging(real) as gauges:
                pre, nc = fwd(params, net_state, x,
                              _attach(pools, block, pos, maxp, lanes=lanes))
            with jax.named_scope("sample"):
                logits = pre[:, -1].astype(jnp.float32)
                nxt = sample_tokens(logits, keys, token_idx, temps, top_ks,
                                    top_ps)
            return _strip(nc, pools), _with_counts(nxt.astype(jnp.int32),
                                                   counts, gauges)

        return decode_step

    def _make_prefill(self, bucket: int):
        fwd, encode = self._fwd, self._encode
        maxp = self.pages_per_slot if self.ring else None
        state = self.state

        def prefill(params, net_state, pools, block, start, last_idx,
                    tokens, keys, token_idx, temps, top_ks, top_ps, ids,
                    slot):
            """One request's prompt suffix ([1, bucket]) + first sample.
            ``start`` [1] is the suffix's global start position (0, or
            the shared-prefix length); ``last_idx`` () indexes the last
            REAL token inside the bucket — bucket padding beyond it
            writes scratch rows that the causal mask hides until decode
            overwrites it position by position.  ``ids`` [S] comes back
            with the sample at lane ``slot``: the decode step's tokens."""
            x = encode(tokens)

            def real():      # the prompt's own tokens, not the padding
                return jnp.arange(bucket)[None] <= last_idx

            with counting(real) as counts, gauging(real) as gauges:
                pre, nc = fwd(params, net_state, x,
                              _attach(pools, block, start, maxp,
                                      (last_idx + 1)[None],
                                      (slot + 1)[None] if state else None))
            with jax.named_scope("sample"):
                logits = jnp.take(pre[0], last_idx, axis=0)[None]
                tok = sample_tokens(logits.astype(jnp.float32), keys,
                                    token_idx, temps, top_ks, top_ps)
            tok = tok.astype(jnp.int32)
            return (_strip(nc, pools), _with_counts(tok, counts, gauges),
                    ids.at[slot].set(tok[0]))

        # the compiled module's name, so the profiler's ``XLA Modules`` line
        # reads ``jit_prefill_512`` and tells the buckets apart
        prefill.__name__ = prefill.__qualname__ = f"prefill_{bucket}"
        return prefill

    def _make_read_page(self):
        def read_page(pools, page):
            """One page's slice of every paged pool's arrays (the offload
            side of the host tier; state pools have no pages)."""
            return map_pools(
                lambda c: {k: jax.lax.dynamic_index_in_dim(
                    a, page, 0, keepdims=False) for k, a in c.items()},
                _paged_only(pools))

        return read_page

    def _make_write_page(self):
        def write_page(pools, page, payload):
            """One page's slices back into every paged pool (the restore
            side); pools are donated, so the write is in place, and a state
            pool goes back as it came."""
            written = map_pools(
                lambda c, p: {k: jax.lax.dynamic_update_index_in_dim(
                    a, p[k].astype(a.dtype), page, 0)
                    for k, a in c.items()},
                _paged_only(pools), payload)
            return _overlay(pools, written)

        return write_page

    # ------------------------------------------------------------- dispatch
    def decode(self, params, net_state, pools, block, pos, tokens, keys,
               token_idx, temps, top_ks, top_ps, expected: bool = False):
        if self.detector is not None:
            self.detector.check(("decode", tokens, pos, block), {},
                                expected=expected)
        return self._decode(params, net_state, pools, block, pos, tokens,
                            keys, token_idx, temps, top_ks, top_ps)

    def prefill(self, bucket, params, net_state, pools, block, start,
                last_idx, tokens, keys, token_idx, temps, top_ks, top_ps,
                ids, slot, expected: bool = False):
        """``(pools, sampled, ids)``: ``sampled`` the first token [1] (or
        ``(token, counts)``), ``ids`` with it at lane ``slot``."""
        if self.detector is not None:
            self.detector.check((f"prefill_{bucket}", tokens, start, ids),
                                {}, expected=expected)
        return self._prefill[bucket](
            params, net_state, pools, block, start, last_idx, tokens,
            keys, token_idx, temps, top_ks, top_ps, ids, slot)

    def read_page(self, pools, page: int, expected: bool = False):
        """Device → host: one page of every pool as a numpy payload."""
        if self.detector is not None:
            self.detector.check(("read_page",), {}, expected=expected)
        return jax.device_get(self._read_page(pools, np.int32(page)))

    def write_page(self, pools, page: int, payload,
                   expected: bool = False):
        """Host → device: write a payload into page ``page``; returns
        the new pools (the old ones are donated/consumed)."""
        if self.detector is not None:
            self.detector.check(("write_page",), {}, expected=expected)
        return self._write_page(pools, np.int32(page), payload)

    def page_nbytes(self, pools) -> int:
        """Host bytes one offloaded page costs (one page of every pool
        array) — the unit of the prefix cache's host-tier budget."""
        return sum(a.nbytes // a.shape[0]
                   for a in jax.tree_util.tree_leaves(_paged_only(pools)))

    # --------------------------------------------------------------- warmup
    def _compute_programs(self) -> Dict[str, Tuple]:
        """``{name: (jitted, args after the pools)}`` for the programs that
        run the model — each ``prefill_<bucket>`` and ``decode`` — with the
        exact arguments they are warmed, and therefore served, with: NumPy
        mirrors, and the ids vector a device array."""
        s, maxp = self.slots, self.pages_per_slot + self.ring
        z, ids = np.zeros, self.fresh_ids()
        progs = {
            f"prefill_{b}": (self._prefill[b], (
                z((1, maxp), np.int32), z((1,), np.int32), np.int32(0),
                z((1, b), np.int32), z((1, 2), np.uint32), z((1,), np.int32),
                z((1,), np.float32), z((1,), np.int32),
                np.ones((1,), np.float32), ids, np.int32(0)))
            for b in self.prefill_buckets}
        progs["decode"] = (self._decode, (
            z((s, maxp), np.int32), z((s,), np.int32), ids,
            z((s, 2), np.uint32), z((s,), np.int32), z((s,), np.float32),
            z((s,), np.int32), np.ones((s,), np.float32)))
        return progs

    def _log_tiling(self) -> None:
        """How each compute program's layers run their kernels
        (``Layer.describe_serving``), each distinct line once a program.
        The choice is static per program, a function of its shapes, so
        this is the whole account of how the kernels engaged."""
        for name in dict.fromkeys(name for name, _ in self.calls):
            said = dict.fromkeys(
                line for zero in (False, True) for l in self._layers
                if (line := l.describe_serving(self.calls[(name, zero)]))
                is not None)
            program = name if name == "decode" else f"prefill_{name}"
            for line in said:
                logger.info("generation.%s: %s", program, line)

    def lowered(self) -> Dict[str, "jax.stages.Lowered"]:
        """Each compute program lowered at its serving signature (abstract
        pools; nothing executes, nothing is donated) — how a caller reads
        which kernels a program was built from BEFORE it is compiled:
        ``.as_text()`` for the kernel names.  It needs this object and its
        net's real parameters.  For the compiled module — its instructions
        and the scope each lies under — ask ``observability.recompile.
        program_scopes("generation.<name>")``, which needs neither and
        still answers after the engine has let the version go; these are the
        two ways a program is described, and there is no third."""
        head = (self.serving_params(), self.net.net_state,
                jax.eval_shape(self.fresh_pools))
        return {name: jitted.lower(*head, *tail)
                for name, (jitted, tail) in self._compute_programs().items()}

    def warm(self) -> int:
        """AOT-compile every program on a SCRATCH pool (donation consumes
        it; the live pool is never touched) through the version's
        detector as planned compiles.  Returns the number of programs
        warmed — after this, steady-state serving compiles nothing.

        Warmup is also where the compute programs learn to describe
        themselves: ``decode`` and each ``prefill_<bucket>`` are handed to
        ``observability.recompile.register_program`` as
        ``generation.<name>`` with the exact arguments they are warmed with
        (abstracted there: shapes, dtypes and shardings, no array), so that
        ``program_scopes("generation.decode")`` can give the compiled
        module's instructions by scope at any later time.  (``lowered()``
        is the other, earlier view: the lowering's kernel names, from this
        object and real parameters.)

        Warmup is also the memory-observability hook: the pool /
        params ledger is recorded here (metadata walk), and when a
        ``ShardStatsCollector`` is installed each program additionally
        gets its HLO memory + collective census (abstract lowering on
        the scratch args, BEFORE they are donated).  Cost note: the
        census ``lower().compile()`` does not share jit's dispatch
        cache, so a collector-on warmup compiles each program once more
        — the same documented one-off-per-signature price the
        ``StepProfiler`` cost-analysis seam pays (profiling.py), only
        ever while the opt-in collector is installed."""
        from deeplearning4j_tpu.observability import shardstats
        from deeplearning4j_tpu.observability.recompile import (
            register_program,
        )

        params, net_state = self.serving_params(), self.net.net_state
        pools = self.fresh_pools()
        shardstats.record_ledger(
            "generation",
            {"params": self.net.params,
             # what the snapshot costs: its copies, not the shared leaves
             "serving_params": jax.tree_util.tree_map(
                 lambda s, p: None if s is p else s, params,
                 self.net.params),
             "net_state": net_state, "kv_pools": pools})
        progs = self._compute_programs()
        for name, (jitted, tail) in progs.items():
            register_program(f"generation.{name}", jitted,
                             (params, net_state, pools) + tail)
        self._log_tiling()
        coll = shardstats.active_collector()
        if coll is not None:
            # census at the exact warmup signatures; lower-only, so the
            # scratch pools below are still live for the real dispatches
            for name, (jitted, tail) in progs.items():
                coll.analyze_program(jitted, f"generation.{name}",
                                     (params, net_state, pools) + tail)
        # the ids vector goes from program to program as it does served:
        # fresh into the first, then each program's own output
        ids = self.fresh_ids()
        for b in self.prefill_buckets:
            tail = progs[f"prefill_{b}"][1]
            pools, _, ids = self.prefill(b, params, net_state, pools,
                                         *tail[:-2], ids, tail[-1],
                                         expected=True)
        block, pos, _, *policy = progs["decode"][1]
        for _ in range(2):      # after a prefill, then after a decode step
            pools, tok = self.decode(params, net_state, pools, block, pos,
                                     ids, *policy, expected=True)
            ids = sampled_ids(tok)
        payload = self.read_page(pools, 1, expected=True)
        pools = self.write_page(pools, 1, payload, expected=True)
        jax.block_until_ready(tok)
        del pools
        return len(self.prefill_buckets) + 3
