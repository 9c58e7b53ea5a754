"""Real-TPU test tier (``DL4J_TPU_TESTS=1 python -m pytest tests/test_tpu.py``).

The decisive on-chip facts the CPU tier cannot prove (≙ the reference's
``CuDNNGradientChecks.java:66,114-122`` — helper-vs-builtin parity executed
on the accelerator):

- Pallas kernels compile and run NON-interpreted, matching the stock XLA
  math forward and backward.
- The jitted train step runs with buffer donation on HBM.
- bf16 mixed precision executes on the MXU with fp32 master params.
- A mesh-placed SyncTrainingMaster step executes on the chip.
- Streaming rnnTimeStep and ring attention produce device-correct results.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.tpu


def _lrn_reference(x, k, n, alpha, beta):
    """Stock XLA formula: y = x * (k + alpha * window_sum(x^2))^-beta."""
    half = n // 2
    C = x.shape[-1]
    sq = x * x
    acc = jnp.zeros_like(x)
    for w in range(-half, half + 1):
        lo, hi = max(0, -w), min(C, C - w)
        acc = acc.at[..., lo:hi].add(sq[..., lo + w : hi + w])
    return x * jnp.power(k + alpha * acc, -beta)


def test_on_tpu():
    assert jax.devices()[0].platform == "tpu"


def test_pallas_lrn_forward_compiled():
    from deeplearning4j_tpu.helpers import pallas_ops

    assert not pallas_ops._interpret(), "must compile for real on TPU"
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(32, 96).astype(np.float32) + 0.1)
    got = pallas_ops.lrn(x, 2.0, 5, 1e-4, 0.75)
    want = _lrn_reference(x, 2.0, 5, 1e-4, 0.75)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_pallas_lrn_backward_compiled():
    from deeplearning4j_tpu.helpers import pallas_ops

    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.rand(16, 64).astype(np.float32) + 0.1)

    g_pallas = jax.grad(lambda a: pallas_ops.lrn(a, 2.0, 5, 1e-4, 0.75).sum())(x)
    g_ref = jax.grad(lambda a: _lrn_reference(a, 2.0, 5, 1e-4, 0.75).sum())(x)
    np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-6)


def test_pallas_bn_inference_compiled():
    from deeplearning4j_tpu.helpers import pallas_ops

    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.rand(64, 48).astype(np.float32))
    mean = jnp.asarray(rs.rand(48).astype(np.float32))
    var = jnp.asarray(rs.rand(48).astype(np.float32) + 0.5)
    gamma = jnp.asarray(rs.rand(48).astype(np.float32))
    beta = jnp.asarray(rs.rand(48).astype(np.float32))
    got = pallas_ops.bn_inference(x, mean, var, gamma, beta, 1e-5)
    want = (x - mean) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_pallas_bn_training_compiled():
    from deeplearning4j_tpu.helpers import pallas_ops

    rs = np.random.RandomState(12)
    x = jnp.asarray(rs.randn(32, 24).astype(np.float32))
    gamma = jnp.asarray(rs.randn(24).astype(np.float32))
    beta = jnp.asarray(rs.randn(24).astype(np.float32))
    y, mean, var = pallas_ops.bn_training(x, gamma, beta, 1e-5)
    m, v = x.mean(0), x.var(0)
    want = gamma * (x - m) * jax.lax.rsqrt(v + 1e-5) + beta
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-3, atol=1e-4)
    g = jax.grad(lambda a: pallas_ops.bn_training(a, gamma, beta, 1e-5)[0].sum())(x)
    g_ref = jax.grad(lambda a: (gamma * (a - a.mean(0))
                                * jax.lax.rsqrt(a.var(0) + 1e-5) + beta).sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-2, atol=1e-4)


def test_lenet_train_step_loss_decreases():
    from deeplearning4j_tpu.models.zoo import lenet

    net = lenet(updater="nesterovs", lr=0.01)
    rs = np.random.RandomState(3)
    x = rs.rand(64, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, 64)]
    net.fit(x, y)
    first = net.score_value
    for _ in range(10):
        net.fit(x, y)
    assert net.score_value < first


def test_train_step_donates_buffers():
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    net = MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(4)
         .updater("sgd", learning_rate=0.1).list()
         .layer(DenseLayer(n_in=8, n_out=16))
         .layer(OutputLayer(n_in=16, n_out=4)).build())).init()
    rs = np.random.RandomState(5)
    x = rs.rand(16, 8).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, 16)]
    old_w = net.params["layer_0"]["W"]
    net.fit(x, y)  # jitted step has donate_argnums=(0,1,2)
    assert old_w.is_deleted(), "param buffers must be donated on TPU"


def test_bf16_mixed_precision_on_mxu():
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    net = MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(6)
         .updater("adam", learning_rate=0.01).list()
         .compute_dtype("bfloat16")
         .layer(DenseLayer(n_in=32, n_out=64, activation="relu"))
         .layer(OutputLayer(n_in=64, n_out=4)).build())).init()
    rs = np.random.RandomState(7)
    x = rs.rand(32, 32).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, 32)]
    for _ in range(5):
        net.fit(x, y)
    assert net.params["layer_0"]["W"].dtype == jnp.float32
    assert np.isfinite(net.score_value)
    out = np.asarray(net.output(x))
    assert out.dtype == np.float32


def test_sync_training_master_step_on_chip():
    from deeplearning4j_tpu.backend import device as backend
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models.zoo import lenet
    from deeplearning4j_tpu.parallel import DistributedNetwork, SyncTrainingMaster

    net = lenet()
    mesh = backend.default_mesh(devices=jax.devices()[:1])
    rs = np.random.RandomState(8)
    x = rs.rand(32, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, 32)]
    DistributedNetwork(net, SyncTrainingMaster(mesh=mesh)).fit(
        ListDataSetIterator(DataSet(x, y), 32))
    assert np.isfinite(net.score_value)


def test_rnn_time_step_on_chip():
    from deeplearning4j_tpu.models.zoo import graves_lstm_char_lm

    net = graves_lstm_char_lm(vocab_size=11, hidden=16, layers=1)
    rs = np.random.RandomState(9)
    ids = rs.randint(0, 11, (2, 4))
    x = np.eye(11, dtype=np.float32)[ids]
    full = np.asarray(net.output(x))
    net.rnn_clear_previous_state()
    for t in range(4):
        step = np.asarray(net.rnn_time_step(x[:, t]))
        np.testing.assert_allclose(full[:, t], step, rtol=1e-4, atol=1e-5)


def test_ring_attention_local_matches_exact():
    from deeplearning4j_tpu.backend import device as backend
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.parallel import ring_self_attention
    from jax.sharding import Mesh

    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1)
    mesh = Mesh(dev, (backend.AXIS_DATA, backend.AXIS_MODEL, backend.AXIS_SEQ))
    rs = np.random.RandomState(10)
    q = jnp.asarray(rs.rand(2, 8, 2, 4).astype(np.float32))
    k = jnp.asarray(rs.rand(2, 8, 2, 4).astype(np.float32))
    v = jnp.asarray(rs.rand(2, 8, 2, 4).astype(np.float32))
    got = ring_self_attention(q, k, v, mesh, causal=True)
    want = dot_product_attention(q, k, v, causal=True)
    # TPU einsums accumulate at the MXU's default (bf16-input) precision, so
    # the two op orders agree only to ~1e-3 relative — that is chip-expected
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=2e-3)


def test_resnet_cifar_step_bf16():
    from deeplearning4j_tpu.models.zoo import resnet50

    net = resnet50(height=32, width=32, stem_stride=1, n_classes=10,
                   blocks=(1, 1, 1, 1), compute_dtype="bfloat16")
    rs = np.random.RandomState(11)
    x = {"input": rs.rand(16, 32, 32, 3).astype(np.float32)}
    y = {"fc": np.eye(10, dtype=np.float32)[rs.randint(0, 10, 16)]}
    net.fit(x, y)
    assert np.isfinite(net.score_value)


# The flash kernel is judged against a HIGHEST-precision f32 einsum, not the
# default-precision one: at default precision XLA feeds the MXU single-pass
# bf16 operands too, and on a v5e under jax 0.9.0 its gradients were the
# noisier side (B8 T2048 bf16: dq off by 4.4e-2 of the gradient scale vs the
# kernel's 7e-3; PR 21 chip run).  Budgets: bf16 products carry 2^-9..2^-8
# relative error into an f32 accumulation — observed <= 2.7e-3 of the output
# scale forward and <= 1.03e-2 of the gradient scale backward across these
# shapes; the budgets below leave 2x.
_FWD_BUDGET = 5e-3
_GRAD_BUDGET = 2e-2


def _flash_vs_truth(q, k, v, *, window=None, grads=True):
    from deeplearning4j_tpu.helpers import flash_attention as fa
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention

    def run(attn, *args):
        if not grads:
            return jax.jit(attn)(*args), ()
        return jax.jit(lambda *a: (attn(*a), jax.grad(
            lambda *b: jnp.sum(attn(*b).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(*a)))(*args)

    with jax.default_matmul_precision("highest"):
        want, gwant = run(
            lambda q, k, v: dot_product_attention(
                q, k, v, causal=True, window=window),
            *(t.astype(jnp.float32) for t in (q, k, v)))
    got, ggot = run(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=window), q, k, v)
    _assert_within(got, want, _FWD_BUDGET, f"forward window={window}")
    for name, a, b in zip("qkv", ggot, gwant):
        _assert_within(a, b, _GRAD_BUDGET, f"d{name} window={window}")


def _assert_within(got, want, budget, what):
    got, want = (np.asarray(t.astype(jnp.float32)) for t in (got, want))
    scale = float(np.max(np.abs(want))) + 1e-9
    np.testing.assert_allclose(got / scale, want / scale, rtol=0,
                               atol=budget, err_msg=what)


def test_flash_attention_compiled_parity():
    """The flash kernel compiled on the chip (non-interpret) matches exact
    attention forward and backward."""
    from deeplearning4j_tpu.helpers import flash_attention as fa

    assert not fa._interpret(), "must compile for real on TPU"
    rs = np.random.RandomState(12)
    q, k, v = (jnp.asarray(rs.randn(2, 512, 4, 64).astype(np.float32) * 0.3)
               for _ in range(3))
    _flash_vs_truth(q, k, v)


def test_flash_attention_at_scale_matches_exact():
    """bq512/bk1024 fwd+bwd at B8 T2048 D128 bf16 — the shape of the d1024
    train step — compiles and matches exact attention.  (How much faster
    than the einsum path it is belongs to the benchmark, not to a test.)"""
    rs = np.random.RandomState(13)
    q, k, v = (jnp.asarray(rs.randn(8, 2048, 8, 128).astype(np.float32) * 0.3,
                           dtype=jnp.bfloat16) for _ in range(3))
    _flash_vs_truth(q, k, v)


def test_ulysses_flash_composes_with_shard_map():
    """Compiled flash attention under shard_map (1-device 'seq' mesh): the
    multi-host Ulysses path routes its local attention through the Pallas
    kernel on TPU — this is the composition a pod run depends on."""
    from jax.sharding import Mesh

    from deeplearning4j_tpu.backend import device as backend
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.parallel import ring_self_attention

    devs = np.array(jax.devices()[:1]).reshape(1, 1, 1)
    mesh = Mesh(devs, (backend.AXIS_DATA, backend.AXIS_MODEL, backend.AXIS_SEQ))
    rng = np.random.default_rng(14)
    q = jnp.asarray(rng.standard_normal((2, 512, 4, 64)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((2, 512, 4, 64)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((2, 512, 4, 64)), jnp.float32)
    got = ring_self_attention(q, k, v, mesh, causal=True, impl="ulysses")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True))(q, k, v)
    _assert_within(got, want, _FWD_BUDGET, "ulysses forward")


def test_flash_attention_windowed_compiled_parity():
    """Sliding-window flash compiled on the chip matches exact banded
    attention — the two-sided index clamps must be Mosaic-correct, not
    just interpreter-correct."""
    rs = np.random.RandomState(15)
    q, k, v = (jnp.asarray(rs.randn(2, 1024, 4, 64).astype(np.float32) * 0.3)
               for _ in range(3))
    for window in (128, 700):
        _flash_vs_truth(q, k, v, window=window)


def test_compiled_decode_scan_on_chip():
    """The one-XLA-program decode (prefill + lax.scan + sampling)
    compiles and runs on the chip; greedy determinism across calls."""
    from deeplearning4j_tpu.models.decode import generate
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    net = transformer_char_lm(vocab_size=64, d_model=64, n_heads=4,
                              layers=2, max_cache=128,
                              compute_dtype="bfloat16")
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, 64, (4, 8))
    a = generate(net, prompt, 32, temperature=0.0)
    b = generate(net, prompt, 32, temperature=0.0)
    assert a.shape == (4, 32)
    np.testing.assert_array_equal(a, b)


def test_scanned_fit_matches_per_step_on_chip():
    """The K-step ``lax.scan`` window compiles and runs on the chip and
    takes the same K updates as the per-step path: same seed, same batch,
    same key stream -> the same losses.  (Whether it is faster belongs to
    the benchmark.)"""
    from deeplearning4j_tpu.models.zoo import lenet

    K = 8
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(128, 28, 28, 1).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rs.randint(0, 10, 128)])

    net = lenet(updater="nesterovs", lr=0.01)
    step = net._get_train_step()
    s = [net.params, net.updater_state, net.net_state]
    per_step = []
    for i in range(K):
        s[0], s[1], s[2], loss, _ = step(
            s[0], s[1], s[2], jnp.asarray(float(i)), x, y,
            net._keys.next(), None, None, None)
        per_step.append(float(loss))

    net2 = lenet(updater="nesterovs", lr=0.01)
    scanned = net2._make_scanned_step()
    _, _, _, losses = scanned(
        net2.params, net2.updater_state, net2.net_state, jnp.zeros(()),
        jnp.broadcast_to(x, (K,) + x.shape),
        jnp.broadcast_to(y, (K,) + y.shape),
        jnp.stack([net2._keys.next() for _ in range(K)]))
    scanned_losses = np.asarray(jax.device_get(losses))
    assert np.all(np.isfinite(scanned_losses))
    assert scanned_losses[-1] < scanned_losses[0]
    # f32 convolutions run at the MXU's default (bf16-pass) precision and
    # XLA may tile them differently inside the scan body, so the two
    # trajectories agree to bf16-level relative error, not bitwise
    np.testing.assert_allclose(scanned_losses, per_step, rtol=2e-2)


# ---------------------------------------------------------------------------
# the serving-path kernels, compiled (first compiled on a chip in PR 21)
# ---------------------------------------------------------------------------

def _bf16_atol(ref, ulps=2):
    """``ulps`` bf16 units in the last place at the reference's largest
    magnitude.  Both sides take bf16 inputs, accumulate in f32 and round
    the result to bf16 (8 bits of mantissa), in a different order of
    operations (online softmax / one fused pass vs the stock lowering), so
    they may land on neighbouring bf16 values and no closer."""
    top = float(np.max(np.abs(np.asarray(ref, np.float32))))
    return ulps * 2.0 ** (np.floor(np.log2(max(top, 1e-30))) - 7)


@pytest.mark.parametrize("name,b,t,hq,hkv", [
    ("mha_t1", 16, 1, 8, 8), ("gqa_t1", 16, 1, 8, 2),
    ("prefill_t16", 1, 16, 8, 8), ("prefill_t128", 1, 128, 8, 8)])
def test_paged_attention_compiled_parity(name, b, t, hq, hkv):
    """``impl="pallas"`` compiled (not interpreted) against the gather
    oracle at the serving shapes: bf16, head_dim 128, page 16."""
    from deeplearning4j_tpu.helpers import paged_attention as pa

    assert not pa._interpret(), "must compile for real on TPU"
    d, ps, maxp = 128, 16, 32
    rs = np.random.RandomState(21)
    pages = b * maxp + 1
    q = jnp.asarray(rs.randn(b, t, hq, d), jnp.bfloat16)
    pk = jnp.asarray(rs.randn(pages, hkv, ps, d), jnp.bfloat16)
    pv = jnp.asarray(rs.randn(pages, hkv, ps, d), jnp.bfloat16)
    block = jnp.asarray(1 + np.arange(b * maxp).reshape(b, maxp), jnp.int32)
    start = rs.randint(0, maxp * ps - t, size=(b,))
    start[0] = 0                           # a row at the very first position
    qpos = jnp.asarray(start[:, None] + np.arange(t)[None], jnp.int32)
    out = jax.jit(lambda *a: pa.paged_decode_attention(
        *a, impl="pallas", interpret=False))(q, pk, pv, block, qpos)
    ref = jax.jit(lambda *a: pa.paged_decode_attention(
        *a, impl="gather"))(q, pk, pv, block, qpos)
    out32, ref32 = (np.asarray(a.astype(jnp.float32)) for a in (out, ref))
    assert np.all(np.isfinite(out32))
    np.testing.assert_allclose(out32, ref32, rtol=0, atol=_bf16_atol(ref32))


@pytest.mark.parametrize("heads,maxp", [(32, 48), (64, 72)])
def test_latent_pages_compiled_parity(heads, maxp):
    """``latent_paged_attention`` compiled against ``pool[block]`` +
    ``_absorbed`` at the two served latent shapes (width 640, value 512,
    page 64, bf16): rows at position 0, at a block's last and the next
    block's first key, at the table's end, and drawn."""
    from deeplearning4j_tpu.helpers import paged_attention as pa
    from deeplearning4j_tpu.nn.layers.latent_attention import (
        LatentAttentionLayer)

    assert not pa._interpret(), "must compile for real on TPU"
    layer = LatentAttentionLayer(n_in=64, n_out=64, n_heads=heads, q_rank=16,
                                 kv_rank=512, nope_dim=128, rope_dim=64,
                                 v_dim=128)
    b, ps, w = 16, 64, 640
    ppb = pa.paged_tiling(b, 1, heads, 1, w, ps, maxp, jnp.bfloat16, 512)[0]
    rs = np.random.RandomState(38)
    qlast = rs.randint(0, maxp * ps, size=(b,))
    qlast[:4] = [0, ppb * ps - 1, ppb * ps, maxp * ps - 1]
    block = 1 + rs.permutation(b * maxp).reshape(b, maxp)
    for i in range(b):
        block[i, qlast[i] // ps + 1:] = 0
    pool = rs.randn(b * maxp + 1, ps, w)
    pool[..., 576:] = 0
    pool = jnp.asarray(pool, jnp.bfloat16)
    params = {"Wkvb": jnp.asarray(0.05 * rs.randn(512, heads * 256),
                                  jnp.bfloat16)}
    q_nope = jnp.asarray(rs.randn(b, 1, heads, 128), jnp.bfloat16)
    q_rope = jnp.asarray(rs.randn(b, 1, heads, 64), jnp.bfloat16)
    block = jnp.asarray(block, jnp.int32)
    qpos = jnp.asarray(qlast[:, None], jnp.int32)
    helper = pa.PagedAttentionHelper()
    assert helper.supports_latent(w, ps, jnp.bfloat16)
    out = jax.jit(lambda *a: layer._absorbed_paged(*a, helper))(
        params, q_nope, q_rope, pool, block, qpos)
    ref = jax.jit(lambda p, qn, qr, pc, blk, pos: layer._absorbed(
        p, qn, qr, pc[blk].reshape(b, -1, w), pos))(
            params, q_nope, q_rope, pool, block, qpos)
    out32, ref32 = (np.asarray(a.astype(jnp.float32)) for a in (out, ref))
    assert np.all(np.isfinite(out32))
    np.testing.assert_allclose(out32, ref32, rtol=0, atol=_bf16_atol(ref32))


def test_paged_attention_rejects_untileable_page_size():
    """A bf16 page needs 16 rows to fill a tile; 8 must fail loudly at
    trace time, not at the first request."""
    from deeplearning4j_tpu.helpers import paged_attention as pa

    q = jnp.zeros((2, 1, 4, 128), jnp.bfloat16)
    pool = jnp.zeros((9, 4, 8, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="page_size=8"):
        pa.paged_decode_attention(
            q, pool, pool, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2, 1), jnp.int32), impl="pallas")


@pytest.mark.parametrize("name,rows,dtype,res_mask", [
    ("decode", 16, "bfloat16", False), ("cap_f32", 1024, "float32", True),
    ("cap_bf16", 1024, "bfloat16", True)])
def test_dropout_residual_norm_compiled_parity(name, rows, dtype, res_mask):
    """The fused norm compiled at a decode shape (16 slots x d1024) and at
    its VMEM cap (1024 x 1024 with residual and mask) against plain jnp."""
    from deeplearning4j_tpu.helpers import fused_epilogue as fe

    assert not fe._interpret(), "must compile for real on TPU"
    cols, dt = 1024, jnp.dtype(dtype)
    assert fe.FusedEpilogueHelper().supports(jnp.zeros((rows, cols), dt))
    rs = np.random.RandomState(22)
    h = jnp.asarray(rs.randn(rows, cols), dt)
    res = jnp.asarray(rs.randn(rows, cols), dt) if res_mask else None
    gamma = jnp.asarray(rs.rand(cols) + 0.5, dt)
    beta = jnp.asarray(rs.randn(cols), dt)
    mask = jnp.asarray(rs.rand(rows, cols) > 0.1) if res_mask else None
    out = jax.jit(lambda h, r, g, b, m: fe.dropout_residual_norm(
        h, r, g, b, rate=0.1, mask=m))(h, res, gamma, beta, mask)
    x = h.astype(jnp.float32)
    if res_mask:
        x = x + res.astype(jnp.float32)
    mu, var = x.mean(-1, keepdims=True), x.var(-1, keepdims=True)
    ref = ((x - mu) * jax.lax.rsqrt(var + 1e-5) * gamma.astype(jnp.float32)
           + beta.astype(jnp.float32))
    if res_mask:
        ref = ref * mask / 0.9
    out32, ref32 = np.asarray(out.astype(jnp.float32)), np.asarray(ref)
    # f32: same math, different reduction order; bf16: output rounding
    atol = 1e-5 if dtype == "float32" else _bf16_atol(ref32)
    np.testing.assert_allclose(out32, ref32, rtol=0, atol=atol)
