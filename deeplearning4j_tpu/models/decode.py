"""On-device autoregressive generation: prefill + ``lax.scan`` decode.

``utils.sampling.sample_sequence`` mirrors the reference's host-side
sampling loop (the DL4J GravesLSTM example's ``sampleCharactersFromNetwork``
over ``rnnTimeStep``) — one dispatch per token, which on an accelerator is
dominated by host round-trip latency.  This module is the TPU-native fast path:
the whole generation — prompt prefill, per-token forward through the KV
caches / recurrent carries, logit filtering, and the categorical draw — is
ONE jitted XLA program, with the token loop as ``lax.scan``.  Decode cost
is then what the hardware actually charges: streaming the KV cache through
HBM (the bandwidth GQA and rolling-window caches exist to shrink).

Works for both model families exactly like ``rnn_time_step``: attention
layers carry KV caches, recurrent layers carry hidden state.
``MultiLayerNetwork`` and single-input/single-output ``ComputationGraph``
both compile (reference streaming inference
``MultiLayerNetwork.rnnTimeStep`` :2195 and
``ComputationGraph.rnnTimeStep`` :1674); multi-input graphs keep the host
loop (``utils.sampling.sample_sequence``) — generation feeds back ONE
token stream, so a single input is the only well-defined case.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the ONE sampling-policy implementation, shared with the host loop and
# the continuous-batching generation engine (utils.sampling owns it so
# temperature/top-k/top-p can never diverge across the decode paths)
from deeplearning4j_tpu.utils.sampling import _sampler  # noqa: F401


def _last_logits_fwd(net):
    """(params, net_state, x, carries) -> (preoutput, new_carries) for
    either model family — the one seam the decode scan needs."""
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork

    if isinstance(net, MultiLayerNetwork):
        def fwd(params, net_state, x, carries):
            pre, _, _, new_carries = net._forward(
                params, net_state, x, train=False, rng=None,
                carries=carries or None)
            return pre, new_carries
        return fwd

    in_name, out_name = _cg_single_io(net)

    def fwd(params, net_state, x, carries):
        acts, _, new_carries = net._forward(
            params, net_state, {in_name: x}, train=False, rng=None,
            carries=carries or None)
        return acts[out_name], new_carries

    return fwd


def _cg_single_io(net):
    """The single input/output names of a generation-capable graph."""
    if len(net.conf.inputs) != 1 or len(net.conf.outputs) != 1:
        raise ValueError(
            "compiled decode needs a single-input single-output "
            f"ComputationGraph (got {len(net.conf.inputs)} inputs, "
            f"{len(net.conf.outputs)} outputs); use "
            "utils.sampling.sample_sequence for multi-stream graphs")
    return net.conf.inputs[0], net.conf.outputs[0]


def _ids_need_time_axis(net, one_hot: bool) -> bool:
    """True when id inputs must carry a trailing singleton axis so a
    ``collapse_column`` EmbeddingLayer reads [B, T, 1] as T column steps —
    without it a [B, 1] per-token feed collapses to a rank-2 column embed
    and the time axis is lost (``rnn_time_step`` does the same expansion:
    sequential.py / graph.py id rules)."""
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.layers.dense import EmbeddingLayer

    if one_hot:
        return False
    if isinstance(net, MultiLayerNetwork):
        l0 = net.layers[0] if net.layers else None
        return isinstance(l0, EmbeddingLayer) and l0.collapse_column
    emb = net._id_consumer(_cg_single_io(net)[0])
    return emb is not None and emb.collapse_column


def build_decode_fn(net, steps: int, *, temperature: float = 1.0,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    one_hot: bool = False,
                    vocab_size: Optional[int] = None,
                    expand_ids: Optional[bool] = None):
    """Pure generation function for ``net`` (jit it once, call many times).

    Returns ``fn(params, net_state, carries, prompt, rng) -> (ids, carries)``
    where ``prompt`` is [B, T_prompt] int ids, ``carries`` are freshly
    seeded streaming caches (see ``models.common.seed_stream_caches``; may
    be ``{}`` for purely recurrent nets), and ``ids`` is the [B, steps]
    sampled continuation.  The first token is drawn from the prompt's last
    logits; each subsequent token from its predecessor's logits.

    Returned-carries contract: the caches reflect the prompt plus the first
    ``steps - 1`` sampled tokens — the FINAL sampled token is never fed back
    (its logits are never needed), for every ``steps`` including 1.  A
    caller resuming generation from the returned carries must therefore
    feed ``ids[:, -1]`` as the next input; total cache occupancy after a
    call is ``t_prompt + steps - 1`` positions.
    """
    if steps < 1:
        raise ValueError(f"steps={steps} must be >= 1")
    if one_hot and vocab_size is None:
        raise ValueError("one_hot decoding needs vocab_size")
    if expand_ids is None:
        expand_ids = _ids_need_time_axis(net, one_hot)
    sample = _sampler(temperature, top_k, top_p)

    def encode(tok):
        # tok: [B] ids -> one network step of input
        if one_hot:
            return jax.nn.one_hot(tok, vocab_size, dtype=jnp.float32)[:, None]
        # collapse_column embeddings read [B, 1, 1] as one timestep column
        return tok[:, None, None] if expand_ids else tok[:, None]

    fwd = _last_logits_fwd(net)

    def fn(params, net_state, carries, prompt, rng):
        if one_hot:
            x = jax.nn.one_hot(prompt, vocab_size, dtype=jnp.float32)
        else:
            x = prompt[..., None] if expand_ids else prompt
        pre, carries = fwd(params, net_state, x, carries)
        logits0 = pre[:, -1].astype(jnp.float32)
        keys = jax.random.split(rng, steps)
        tok0 = sample(logits0, keys[0])

        def step(carry, key):
            tok, carries = carry
            pre, carries = fwd(params, net_state, encode(tok), carries)
            tok = sample(pre[:, -1].astype(jnp.float32), key)
            return (tok, carries), tok

        if steps == 1:
            return tok0[:, None], carries
        (_, carries), rest = lax.scan(step, (tok0, carries), keys[1:])
        ids = jnp.concatenate([tok0[None], rest], axis=0)   # [steps, B]
        return jnp.transpose(ids), carries

    return fn


def generate(net, prompt_ids, steps: int, *, temperature: float = 1.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None,
             one_hot: Optional[bool] = None,
             vocab_size: Optional[int] = None) -> np.ndarray:
    """Generate ``steps`` tokens after ``prompt_ids`` — same contract as
    ``utils.sampling.sample_sequence`` but compiled end-to-end (the whole
    loop is one XLA program; per-token Python dispatch is gone).  Accepts
    a ``MultiLayerNetwork`` or a single-input/single-output
    ``ComputationGraph`` (multi-stream graphs: use the host loop).

    The decode function is cached on the net per (steps, sampling policy,
    prompt shape), so repeated calls skip retracing.
    """
    from deeplearning4j_tpu.models.common import (
        check_cache_capacity, seed_stream_caches,
    )
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.utils.sampling import _resolve_encoding

    if isinstance(net, MultiLayerNetwork):
        named_layers = [(l.name, l) for l in net.layers]
    else:
        _cg_single_io(net)  # generation feeds back ONE token stream
        named_layers = [(n, net.nodes[n].layer) for n in net.topo
                        if net.nodes[n].layer is not None]
    prompt_ids, one_hot, vocab_size = _resolve_encoding(
        net, prompt_ids, one_hot, vocab_size)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    b, t_prompt = prompt_ids.shape
    carries = seed_stream_caches(named_layers, {}, b,
                                 net.conf.compute_dtype)
    # the WHOLE generation must fit the linear caches; checked host-side
    # once — no per-token position sync (rolling caches never overflow).
    # Occupancy is t_prompt + steps - 1: the final sampled token is never
    # fed back through the cache (see build_decode_fn's carries contract).
    check_cache_capacity(carries, t_prompt + steps - 1, pos=0)

    key = ("decode", steps, temperature, top_k, top_p, one_hot, vocab_size,
           b, t_prompt)
    jitted = net._jit_cache.get(key)
    if jitted is None:
        # carries (arg 2) are freshly seeded per call and discarded after:
        # donating lets XLA write the KV caches in place from the start
        # instead of copying the zero-seeded buffers (cache-sized saving
        # at TPU decode configs)
        jitted = jax.jit(build_decode_fn(
            net, steps, temperature=temperature, top_k=top_k, top_p=top_p,
            one_hot=one_hot, vocab_size=vocab_size), donate_argnums=(2,))
        net._jit_cache[key] = jitted
    ids, _ = jitted(net.params, net.net_state, carries,
                    jnp.asarray(prompt_ids), rng)
    return np.asarray(ids)
