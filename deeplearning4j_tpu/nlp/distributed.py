"""Distributed embedding training — the Spark-NLP scaleout redesigned.

Reference: ``spark/dl4j-spark-nlp/.../word2vec/Word2Vec.java:61,130-195`` —
TextPipeline builds the vocab with Spark accumulators, the driver broadcasts
vocab + exp table, executors run First/SecondIterationFunction over their
partitions, and syn0 is averaged across partitions at the end.

TPU-native redesign: no driver/executor split and no parameter shipping.
 * vocab build: multithreaded host-side counting (the accumulator analog);
 * training: every pair batch is sharded over the mesh 'data' axis with
   ``shard_map``; each device runs the SAME batched kernel
   (``nlp/learning.py``: gather → MXU einsum → scatter-add) on its shard and
   the resulting parameter deltas are ``pmean``-ed over ICI — the
   per-partition-average semantics of the reference, applied every batch
   instead of once per epoch, so quality matches single-process training;
 * determinism: same seed ⇒ same pair stream ⇒ same result for any mesh
   size whose pmean ordering is fixed (XLA all-reduce is deterministic).

``DistributedWord2Vec`` on a 1-device mesh reproduces ``Word2Vec`` exactly
(the equivalence oracle, ≙ TestSparkWord2Vec-style parity).
"""

from __future__ import annotations

import math
import threading
from collections import Counter
from functools import partial
from typing import Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.backend import device as backend
from deeplearning4j_tpu.nlp import learning
from deeplearning4j_tpu.nlp.documents import SentenceIterator
from deeplearning4j_tpu.nlp.sequencevectors import VectorsConfiguration
from deeplearning4j_tpu.nlp.tokenization import (
    DefaultTokenizerFactory, TokenizerFactory,
)
from deeplearning4j_tpu.nlp.vocab import VocabCache, VocabWord
from deeplearning4j_tpu.nlp.glove import Glove
from deeplearning4j_tpu.nlp.word2vec import Word2Vec


def parallel_vocab_count(sentences: List[str],
                         tokenizer_factory: TokenizerFactory,
                         n_threads: int = 4) -> Counter:
    """Multithreaded token counting — the TextPipeline accumulator analog
    (``spark/text/functions/TextPipeline.java``)."""
    chunks = np.array_split(np.asarray(sentences, dtype=object),
                            max(n_threads, 1))
    counters = [Counter() for _ in chunks]

    def count(i):
        tf = tokenizer_factory
        for s in chunks[i]:
            counters[i].update(tf.create(str(s)).tokens())

    threads = [threading.Thread(target=count, args=(i,))
               for i in range(len(chunks))]
    [t.start() for t in threads]
    [t.join() for t in threads]
    total = Counter()
    for c in counters:
        total.update(c)
    return total


class DistributedWord2Vec(Word2Vec):
    """Word2Vec whose batch kernel runs SPMD over a device mesh.

    Batches are zero-padded to a multiple of the mesh's 'data' axis size;
    padded rows carry mask 0 so they contribute nothing (same masking the
    serial engine uses for its power-of-two padding).
    """

    def __init__(self, config: VectorsConfiguration,
                 sentence_iterator: SentenceIterator,
                 tokenizer_factory: Optional[TokenizerFactory] = None,
                 mesh: Optional[Mesh] = None):
        super().__init__(config, sentence_iterator,
                         tokenizer_factory or DefaultTokenizerFactory())
        self.mesh = mesh or backend.default_mesh()
        axis = self.mesh.axis_names[0]
        self._axis = axis
        self._sharded_steps = {}

    # Which rows of each parameter matrix a kernel touches, and with what
    # occurrence weights — needed to convert per-shard collision-mean deltas
    # back into the exact global mean (see _get_sharded).
    @staticmethod
    def _row_specs(name, sharded):
        def bcast(mask, idx2d):
            return jnp.broadcast_to(mask[:, None], idx2d.shape).reshape(-1)

        if name == "sg_ns":
            inputs, targets, negs, mask = sharded
            out = jnp.concatenate([targets[:, None], negs], 1)
            return (inputs, mask), (out.reshape(-1), bcast(mask, out))
        if name == "sg_hs":
            inputs, pts, _cds, code_mask, mask = sharded
            return ((inputs, mask),
                    (pts.reshape(-1), (code_mask * mask[:, None]).reshape(-1)))
        if name == "cbow_ns":
            ctx, ctx_mask, targets, negs, mask = sharded
            out = jnp.concatenate([targets[:, None], negs], 1)
            return ((jnp.maximum(ctx, 0).reshape(-1),
                     (ctx_mask * mask[:, None]).reshape(-1)),
                    (out.reshape(-1), bcast(mask, out)))
        if name == "cbow_hs":
            ctx, ctx_mask, pts, _cds, code_mask, mask = sharded
            return ((jnp.maximum(ctx, 0).reshape(-1),
                     (ctx_mask * mask[:, None]).reshape(-1)),
                    (pts.reshape(-1), (code_mask * mask[:, None]).reshape(-1)))
        raise KeyError(name)

    def _get_sharded(self, name, fn, n_sharded_args):
        """shard_map-wrap one of the learning-step kernels.

        Params stay replicated; batch args shard over the data axis.  The
        kernels apply a collision-MEAN per row over their (local) batch, so
        the per-shard delta is  sum_local/count_local.  Multiplying back by
        the local count, psum-ing both sums and counts over ICI, and
        re-dividing yields  Σsums/Σcounts — the identical update serial
        training computes on the unsharded batch (distributed == local
        math, the reference's equivalence oracle)."""
        key = name
        if key in self._sharded_steps:
            return self._sharded_steps[key]
        axis = self._axis
        mesh = self.mesh
        specs = self._row_specs
        in_specs = (P(), P()) + (P(axis),) * n_sharded_args + (P(),)
        out_specs = (P(), P(), P())

        @partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                 out_specs=out_specs)
        def stepped(a, b, *rest):
            *sharded, lr = rest
            new_a, new_b, loss = fn(a, b, *sharded, lr)
            (ia, wa), (ib, wb) = specs(name, sharded)
            ca = jnp.zeros((a.shape[0],), a.dtype).at[ia].add(wa)
            cb = jnp.zeros((b.shape[0],), b.dtype).at[ib].add(wb)
            ca_tot = jax.lax.psum(ca, axis)
            cb_tot = jax.lax.psum(cb, axis)
            da = (jax.lax.psum((new_a - a) * ca[:, None], axis)
                  / jnp.maximum(ca_tot, 1.0)[:, None])
            db = (jax.lax.psum((new_b - b) * cb[:, None], axis)
                  / jnp.maximum(cb_tot, 1.0)[:, None])
            return a + da, b + db, jax.lax.psum(loss, axis)

        jitted = jax.jit(stepped)
        self._sharded_steps[key] = jitted
        return jitted

    def _pad_to_devices(self, n: int) -> int:
        """Global batch size: power-of-two >= n AND divisible by mesh size."""
        ndev = self.mesh.devices.size
        B = max(self.config.batch_size,
                int(2 ** math.ceil(math.log2(max(n, 1)))))
        return int(np.ceil(B / ndev) * ndev)

    def _apply_batch(self, batch, lr) -> None:
        cfg = self.config
        lk = self.lookup
        n = len(batch["targets"])
        if n == 0:
            return
        B = self._pad_to_devices(n)
        mask = jnp.asarray(self._pad(np.ones(n, np.float32), B))
        targets = jnp.asarray(self._pad(batch["targets"], B))
        lr = jnp.float32(lr)
        if batch["kind"] == "sg":
            inputs = jnp.asarray(self._pad(batch["inputs"], B))
            if cfg.negative > 0:
                negs = lk.sample_negatives(self._next_key(), (B, cfg.negative))
                step = self._get_sharded("sg_ns", learning.sg_ns_step, 4)
                lk.syn0, lk.syn1neg, loss = step(
                    lk.syn0, lk.syn1neg, inputs, targets, negs, mask, lr)
                self.cum_loss += float(loss)
            if cfg.use_hierarchic_softmax:
                pts = jnp.asarray(self._points)[targets]
                cds = jnp.asarray(self._codes)[targets]
                ln = jnp.asarray(self._code_lengths)[targets]
                code_mask = (jnp.arange(self._codes.shape[1])[None, :]
                             < ln[:, None]).astype(jnp.float32)
                step = self._get_sharded("sg_hs", learning.sg_hs_step, 5)
                lk.syn0, lk.syn1, loss = step(
                    lk.syn0, lk.syn1, inputs, pts, cds, code_mask, mask, lr)
                self.cum_loss += float(loss)
        else:  # cbow
            ctx = jnp.asarray(self._pad(batch["contexts"], B, fill=-1))
            ctx_mask = (ctx >= 0).astype(jnp.float32)
            if cfg.negative > 0:
                negs = lk.sample_negatives(self._next_key(), (B, cfg.negative))
                step = self._get_sharded("cbow_ns", learning.cbow_ns_step, 5)
                lk.syn0, lk.syn1neg, loss = step(
                    lk.syn0, lk.syn1neg, ctx, ctx_mask, targets, negs, mask,
                    lr)
                self.cum_loss += float(loss)
            if cfg.use_hierarchic_softmax:
                pts = jnp.asarray(self._points)[targets]
                cds = jnp.asarray(self._codes)[targets]
                ln = jnp.asarray(self._code_lengths)[targets]
                code_mask = (jnp.arange(self._codes.shape[1])[None, :]
                             < ln[:, None]).astype(jnp.float32)
                step = self._get_sharded("cbow_hs", learning.cbow_hs_step, 6)
                lk.syn0, lk.syn1, loss = step(
                    lk.syn0, lk.syn1, ctx, ctx_mask, pts, cds, code_mask,
                    mask, lr)
                self.cum_loss += float(loss)

    class Builder(Word2Vec.Builder):
        def __init__(self):
            super().__init__()
            self._mesh = None

        def mesh(self, mesh: Mesh) -> "DistributedWord2Vec.Builder":
            self._mesh = mesh
            return self

        def build(self) -> "DistributedWord2Vec":
            w2v = super().build()
            return DistributedWord2Vec(
                w2v.config, w2v.sentence_iterator, w2v.tokenizer_factory,
                mesh=self._mesh)


class DistributedGlove(Glove):
    """GloVe whose weighted-least-squares batches shard over the mesh.

    ≙ ``spark/dl4j-spark-nlp/.../glove/Glove.java`` (partition-parallel
    training with per-partition averaging).  Co-occurrence triples shard
    over the data axis; each shard runs the AdaGrad kernel on its slice and
    the parameter/accumulator deltas are pmean-ed — the reference's
    partition-averaged semantics per batch (AdaGrad's nonlinearity makes
    exact serial equivalence impossible here, as it was for Spark)."""

    def __init__(self, *args, mesh: Optional[Mesh] = None, **kw):
        super().__init__(*args, **kw)
        self.mesh = mesh or backend.default_mesh()
        axis = self.mesh.axis_names[0]
        ndev = self.mesh.shape[axis]
        if self.batch_size % ndev:
            self.batch_size = int(np.ceil(self.batch_size / ndev) * ndev)
        mesh_ = self.mesh

        @partial(jax.shard_map, mesh=mesh_,
                 in_specs=(P(),) * 8 + (P(axis),) * 4 + (P(),) * 3,
                 out_specs=(P(),) * 9)
        def stepped(w, wc, b, bc, hw, hwc, hb, hbc, rows, cols, xij, mask,
                    lr, x_max, alpha):
            outs = learning.glove_step(w, wc, b, bc, hw, hwc, hb, hbc,
                                       rows, cols, xij, mask, lr, x_max,
                                       alpha)
            *new_state, loss = outs
            old = (w, wc, b, bc, hw, hwc, hb, hbc)
            averaged = tuple(
                o + jax.lax.pmean(n - o, axis)
                for o, n in zip(old, new_state))
            return averaged + (jax.lax.psum(loss, axis),)

        self._glove_step = jax.jit(stepped)

    class Builder(Glove.Builder):
        def __init__(self):
            super().__init__()
            self._mesh = None

        def mesh(self, mesh: Mesh) -> "DistributedGlove.Builder":
            self._mesh = mesh
            return self

        def build(self) -> "DistributedGlove":
            g = super().build()
            return DistributedGlove(
                sentence_iterator=g.sentence_iterator,
                tokenizer_factory=g.tokenizer_factory,
                layer_size=g.layer_size, window=g.window, epochs=g.epochs,
                learning_rate=g.learning_rate, x_max=g.x_max, alpha=g.alpha,
                min_word_frequency=g.min_word_frequency,
                batch_size=g.batch_size, seed=g.seed, symmetric=g.symmetric,
                mesh=self._mesh)
