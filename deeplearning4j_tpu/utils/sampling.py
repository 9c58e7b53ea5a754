"""Autoregressive sampling on top of the streaming inference API.

≙ the reference's char-modelling example loop (sampleCharactersFromNetwork
in the DL4J GravesLSTM example family: prime the RNN with a prompt via
``rnnTimeStep``, then repeatedly sample from the output distribution and
feed the sample back).  Works unchanged for both model families because
both stream through ``rnn_time_step``: LSTMs carry hidden state,
transformers carry KV caches.

This module is also the ONE owner of the sampling policy (temperature /
top-k / top-p logit filtering + the categorical draw) for every decode
path in the repo: the host loop here, the compiled ``lax.scan`` decode in
``models/decode.py`` (static per-program policy via ``_sampler``), and the
continuous-batching generation engine (per-slot RUNTIME policy arrays via
``sample_tokens`` — one compiled decode step serves requests with mixed
sampling configs).  All three route through ``_filter_logits`` so the
kept-set semantics can never diverge between paths.

What a ``sample_tokens`` step costs follows the batch's policy arrays
(``sampling_path``, decided inside the program, one compiled program):

- ``greedy`` — no row has ``temperature > 0``: one argmax over the
  vocabulary, nothing else;
- ``draw`` — some row draws and no drawing row sets ``top_k >= 1`` or
  ``top_p < 1``: the division by the temperature and the categorical
  draw (Gumbel noise over ``[B, V]``), no sort;
- ``filter`` — a drawing row asks for top-k or top-p: two sorts of
  ``[B, V]``, a softmax and a cumulative sum, for the WHOLE batch.

A greedy row's ``top_k`` / ``top_p`` never count: the kept set does not
change an argmax.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def _filter_logits(logits: jax.Array, top_k=None, top_p=None) -> jax.Array:
    """Standard nucleus/top-k logit filtering: everything outside the kept
    set drops to -inf before the categorical draw.

    ``top_k`` / ``top_p`` are either static Python numbers (validated
    eagerly — the host loop and the compiled-scan decode bake the policy
    into the program) or traced ``[B]`` arrays (the generation engine's
    per-slot policy, one value per running request).  Array semantics:
    ``top_k < 1`` and ``top_p >= 1`` mean "disabled" for that row — the
    runtime analog of passing None, so one compiled program covers every
    per-request mix."""
    neg = jnp.asarray(-1e30, logits.dtype)
    v = logits.shape[-1]
    if top_k is not None:
        if isinstance(top_k, (int, np.integer)):
            if top_k < 1:
                raise ValueError(f"top_k={top_k} must be >= 1")
            k = min(int(top_k), v)   # clamp to vocab
            kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
        else:
            # per-row runtime k: <1 disables (clamps to the full vocab)
            karr = jnp.asarray(top_k, jnp.int32)
            k = jnp.where(karr >= 1, jnp.minimum(karr, v), v)
            sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
            kth = jnp.take_along_axis(sorted_desc, (k - 1)[..., None],
                                      axis=-1)
        logits = jnp.where(logits >= kth, logits, neg)
    if top_p is not None:
        if isinstance(top_p, (float, int, np.floating, np.integer)):
            if not 0.0 < top_p <= 1.0:
                raise ValueError(f"top_p={top_p} must be in (0, 1]; for "
                                 "greedy use temperature=0")
            p = jnp.asarray(top_p, logits.dtype)
        else:
            # per-row runtime p: values >= 1 keep everything (disabled)
            p = jnp.clip(jnp.asarray(top_p, logits.dtype),
                         jnp.finfo(logits.dtype).tiny, 1.0)[..., None]
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative mass >= top_p (always
        # keep the argmax)
        keep_sorted = cum - probs < p
        # threshold = the SMALLEST kept logit
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits,
                                   jnp.asarray(jnp.inf, logits.dtype)),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits >= cutoff, logits, neg)
    return logits


def _sampler(temperature: float, top_k: Optional[int],
             top_p: Optional[float]):
    """Static sampling policy -> pure ``(logits [B, V], key) -> ids [B]``.
    ``temperature <= 0`` means greedy argmax (top-k/top-p ignored — the
    kept set never changes the argmax)."""
    if temperature and temperature > 0:

        def sample(logits, key):
            logits = logits / jnp.asarray(temperature, logits.dtype)
            return jax.random.categorical(
                key, _filter_logits(logits, top_k, top_p), axis=-1)
    else:

        def sample(logits, key):
            return jnp.argmax(logits, axis=-1)

    return sample


# what a batch's sampling epilogue does, cheapest first (sampling_path)
SAMPLING_PATHS = ("greedy", "draw", "filter")


def sampling_path(temperature, top_k, top_p):
    """Index into ``SAMPLING_PATHS`` of the least work that serves every
    row of a batch with these ``[B]`` policy arrays.  Pure and written
    against the array methods numpy and ``jnp`` share: ``sample_tokens``
    calls it on traced arrays to pick its branch, the engine on the
    scheduler's numpy arrays to count ``dl4j_layer_path_steps_total``
    (kind ``head``)."""
    draws = temperature > 0
    filters = draws & ((top_k >= 1) | (top_p < 1))
    return (draws.any().astype(np.int32)
            + filters.any().astype(np.int32))


def sample_tokens(logits: jax.Array, keys: jax.Array, token_idx: jax.Array,
                  temperature: jax.Array, top_k: jax.Array,
                  top_p: jax.Array) -> jax.Array:
    """Per-row runtime sampling for a mixed decode batch.

    ``logits`` [B, V]; ``keys`` [B, 2] uint32 per-REQUEST base keys;
    ``token_idx`` [B] int32 index of the token being drawn (the draw key
    is ``fold_in(base_key, token_idx)``, so a request's stream depends
    only on its seed and position — never on which slot it occupies or
    who else is in the batch); ``temperature`` [B] (<= 0 -> greedy);
    ``top_k`` [B] int32 (< 1 disables); ``top_p`` [B] (>= 1 disables).
    Same policy math as ``_sampler`` row-for-row (shared
    ``_filter_logits``).

    One ``lax.switch`` on ``sampling_path`` of the three arrays runs only
    what the batch asks for; every branch returns, row for row, what the
    ``filter`` branch returns: ``greedy`` and ``draw`` leave out only work
    whose result the last ``where`` discards or a filter that keeps
    everything.  (Up to rounding: a disabled top-p's f32 cumulative sum
    can reach 1 before the last entries and drop a tail of total mass
    under 1e-6 of a 49,152-wide row; ``draw`` keeps that tail.)"""
    temp = jnp.asarray(temperature, logits.dtype)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, logits.dtype)

    def greedy():
        return jnp.argmax(logits, axis=-1)

    def draw(filtered: bool):
        step_keys = jax.vmap(jax.random.fold_in)(keys, token_idx)
        safe_t = jnp.where(temp > 0, temp, jnp.ones_like(temp))
        scaled = logits / safe_t[:, None]
        if filtered:
            scaled = _filter_logits(scaled, top_k, top_p)
        drawn = jax.vmap(
            lambda k, l: jax.random.categorical(k, l, axis=-1))(
            step_keys, scaled)
        return jnp.where(temp > 0, drawn, greedy())

    return jax.lax.switch(
        sampling_path(temp, top_k, top_p),
        (greedy, lambda: draw(False), lambda: draw(True)))


def _resolve_encoding(net, prompt_ids, one_hot: Optional[bool],
                      vocab_size: Optional[int]):
    """Shared preamble for the host sampling loop and on-device generate:
    validate the prompt and resolve the input encoding.  Auto-detection
    covers sequential nets (first layer embedding or not) and
    SINGLE-INPUT ComputationGraphs (the one input either feeds an
    EmbeddingLayer or it doesn't — ``net._id_consumer``); multi-input
    graphs are ambiguous, so those callers must pass ``one_hot=``
    explicitly.  For one-hot CG inputs the vocab width comes from the
    INPUT-side consumer's ``n_in`` (the layer the vector actually feeds),
    never the output head's ``n_out`` — the two differ in
    asymmetric-vocab graphs."""
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.layers.dense import EmbeddingLayer

    prompt_ids = np.asarray(prompt_ids)
    if prompt_ids.ndim != 2:
        raise ValueError(f"prompt_ids must be [B, T], got {prompt_ids.shape}")
    sequential = isinstance(net, MultiLayerNetwork)
    single_in = sequential or len(net.conf.inputs) == 1
    if one_hot is None:
        if sequential:
            one_hot = not (net.layers
                           and isinstance(net.layers[0], EmbeddingLayer))
        elif single_in:
            one_hot = net._id_consumer(net.conf.inputs[0]) is None
        else:
            raise ValueError(
                "one_hot auto-detection needs a single-input net; pass "
                "one_hot= explicitly for a multi-input ComputationGraph")
    if one_hot and vocab_size is None:
        if sequential:
            # input-side rule: the first layer consumes the one-hot vector,
            # so ITS n_in is the width (asymmetric-vocab nets diverge from
            # the head's n_out); head n_out only as a last resort
            vocab_size = (getattr(net.layers[0], "n_in", None)
                          if net.layers else None) or net.layers[-1].n_out
        elif single_in:
            in_name = net.conf.inputs[0]
            consumer = next((net.nodes[n] for n in net.topo
                             if in_name in net.nodes[n].inputs), None)
            layer = getattr(consumer, "layer", None)
            if layer is None or getattr(layer, "n_in", None) is None:
                raise ValueError(
                    "cannot infer the one-hot width: the graph input "
                    f"'{in_name}' feeds a vertex; pass vocab_size=")
            vocab_size = layer.n_in
        else:
            raise ValueError("pass vocab_size= explicitly for a "
                             "multi-input ComputationGraph")
    return prompt_ids, one_hot, vocab_size


def sample_sequence(net, prompt_ids, steps: int, *,
                    temperature: float = 1.0,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    rng: Optional[jax.Array] = None,
                    one_hot: Optional[bool] = None,
                    vocab_size: Optional[int] = None) -> np.ndarray:
    """Generate ``steps`` tokens after priming with ``prompt_ids``.

    prompt_ids: [B, T_prompt] integer array.  ``one_hot`` controls the
    input encoding per step: True feeds one-hot vectors (LSTM char-LM
    configs whose first layer consumes features), False feeds raw ids
    (embedding-first transformers).  Auto-detected from the first layer
    when None.  ``temperature`` <= 0 means greedy argmax; ``top_k`` /
    ``top_p`` (nucleus) filter the distribution before sampling.
    Returns the sampled ids [B, steps].
    """
    prompt_ids, one_hot, vocab_size = _resolve_encoding(
        net, prompt_ids, one_hot, vocab_size)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def encode(ids):
        ids = np.asarray(ids)
        if one_hot:
            return jnp.asarray(np.eye(vocab_size, dtype=np.float32)[ids])
        return jnp.asarray(ids)

    net.rnn_clear_previous_state()
    # prime on the full prompt in one chunk; the last step's distribution
    # seeds the first sample
    probs = net.rnn_time_step(encode(prompt_ids))
    probs = probs[:, -1] if probs.ndim == 3 else probs

    # the one shared policy implementation (also used by the compiled-scan
    # and continuous-batching decode paths); this loop feeds it log-probs,
    # which only differ from the head's logits by a per-row constant the
    # softmax/argmax inside are invariant to
    sample = _sampler(temperature, top_k, top_p)
    out = []
    tok = None
    for _ in range(steps):
        rng, key = jax.random.split(rng)
        tok = sample(jnp.log(jnp.maximum(probs, 1e-30)), key)
        out.append(np.asarray(tok))
        probs = net.rnn_time_step(encode(np.asarray(tok)[:, None]))
        probs = probs[:, -1] if probs.ndim == 3 else probs
    return np.stack(out, axis=1)
