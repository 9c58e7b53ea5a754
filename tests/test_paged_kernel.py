"""Fused-kernel tests (ISSUE 19): the paged decode-attention kernel
(`helpers/paged_attention.py`) and the fused dropout/residual/norm train
epilogue (`helpers/fused_epilogue.py`).

The decode kernel's contract: computing per-row causal attention straight
off the page pool + int32 block tables must match the legacy
gather+softmax oracle (``gather_pages`` + ``paged_attention``) on every
impl (lax fallback, interpreted Pallas) and at every integration level —
raw function, layer-level streaming across a page boundary, and the full
continuous-batching engine under join/leave, prefix-cache-hit, and
hot-swap traffic.  The epilogue's contract: one fused VMEM pass equals
LayerNorm + inverted dropout in jnp, forward and backward, with a
bit-identical bernoulli mask for the same rng key.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeplearning4j_tpu.helpers as helpers
from deeplearning4j_tpu.helpers.fused_epilogue import (
    FusedEpilogueHelper, dropout_residual_norm,
)
from deeplearning4j_tpu.helpers.paged_attention import (
    PAGED_PATHS, SLAB_BLOCK_BYTES, VMEM_BUDGET, PagedAttentionHelper,
    paged_decode_attention, paged_form, paged_latent_attention, paged_path,
    paged_tiling, write_token_rows,
)
from deeplearning4j_tpu.nn.layers.attention import (
    SelfAttentionLayer, gather_pages, paged_attention,
)

pytestmark = pytest.mark.kernels

VOCAB = 29


# --------------------------------------------------------------- scenarios
def _scenario(seed, *, pages, page_size, maxp, b, t, hq, hkv, d,
              dtype=jnp.float32, trash_row=True, qlast=None):
    """Engine-shaped inputs: page 0 is the trash page, unassigned
    block-table slots point at it, per-row positions are mixed (or end
    at ``qlast``, one per row), and (``trash_row``) row 0 is an
    all-padding fresh slot at position 0."""
    rng = np.random.default_rng(seed)
    pool_k = jnp.asarray(
        rng.standard_normal((pages, hkv, page_size, d)), dtype)
    pool_v = jnp.asarray(
        rng.standard_normal((pages, hkv, page_size, d)), dtype)
    q = jnp.asarray(rng.standard_normal((b, t, hq, d)), dtype)
    block = rng.integers(1, pages, size=(b, maxp))
    drawn = rng.integers(t - 1, maxp * page_size, size=(b,))
    qlast = drawn if qlast is None else np.asarray(qlast)
    if trash_row:
        qlast[0] = t - 1
        block[0] = 0
    for bi in range(b):
        live = int(qlast[bi]) // page_size + 1
        block[bi, live:] = 0
    qpos = (qlast - (t - 1))[:, None] + np.arange(t)[None]
    return (q, pool_k, pool_v, jnp.asarray(block, jnp.int32),
            jnp.asarray(qpos, jnp.int32))


def _oracle(q, pk, pv, block, qpos):
    gk = gather_pages(pk, block).astype(q.dtype)
    gv = gather_pages(pv, block).astype(q.dtype)
    return paged_attention(q, gk, gv, qpos)


CONFIGS = {
    "gqa": dict(pages=10, page_size=8, maxp=4, b=3, t=1, hq=4, hkv=2, d=32),
    "mha_chunk": dict(pages=12, page_size=8, maxp=4, b=2, t=2, hq=4,
                      hkv=4, d=64),
    "odd_head_dim": dict(pages=8, page_size=16, maxp=3, b=4, t=1, hq=8,
                         hkv=2, d=48),
    # what the tiling can get wrong.  At page 16 a block is 8 pages (128
    # keys), so a 20-page table ends in a block of 4: rows whose last live
    # page is the first (8), a middle (12) and the last (15) page of block
    # 1, the last page of the table (19), and page 0
    "ragged_blocks": dict(pages=70, page_size=16, maxp=20, b=6, t=1, hq=4,
                          hkv=2, d=32,
                          qlast=[0, 8 * 16, 12 * 16 + 5, 16 * 16 - 1,
                                 20 * 16 - 1, 7]),
    # a chunk behind a prefix: positions start past 0 and the 40 rows
    # split over two row tiles (f32: tiles of 32 positions), the second
    # one part padding
    "chunk_behind_prefix": dict(pages=40, page_size=16, maxp=12, b=2, t=40,
                                hq=4, hkv=2, d=32, trash_row=False,
                                qlast=[37 + 39, 12 * 16 - 1]),
    "page64": dict(pages=20, page_size=64, maxp=5, b=3, t=1, hq=4, hkv=2,
                   d=32),
    "page64_chunk": dict(pages=20, page_size=64, maxp=5, b=2, t=24, hq=8,
                         hkv=2, d=32, trash_row=False,
                         qlast=[100 + 23, 5 * 64 - 1]),
    # the served widths: groups of 9 (starcoder2-7b) and 12 (-3b), head
    # 128, page 16, bf16
    "g9_bf16": dict(pages=40, page_size=16, maxp=12, b=3, t=1, hq=18,
                    hkv=2, d=128, dtype=jnp.bfloat16),
    "g12_bf16_chunk": dict(pages=40, page_size=16, maxp=12, b=1, t=48,
                           hq=24, hkv=2, d=128, dtype=jnp.bfloat16,
                           trash_row=False, qlast=[130 + 47]),
    # a group of ONE row a kv head (multi-head decode: the kernel's
    # ``heads`` form), head 128, page 64: a block is 2 pages, so the
    # 5-page table ends in a block of 1.  Rows: the all-trash row, one
    # that ends mid-page, one at the end of a block's last page, one at
    # the end of the table's last page, one inside a block
    "mha_decode": dict(pages=30, page_size=64, maxp=5, b=5, t=1, hq=6,
                       hkv=6, d=128,
                       qlast=[0, 64 + 10, 2 * 64 - 1, 5 * 64 - 1, 3 * 64 + 5]),
    # olmo-hybrid-7b's 30 heads in bf16
    "mha_decode_bf16": dict(pages=30, page_size=64, maxp=5, b=5, t=1, hq=30,
                            hkv=30, d=128, dtype=jnp.bfloat16,
                            qlast=[0, 64 + 10, 2 * 64 - 1, 5 * 64 - 1,
                                   3 * 64 + 5]),
}


def _tolerance(ref):
    """f32: the two orders of summation.  bf16: both sides take bf16
    inputs, accumulate in f32 and round the result to bf16 in a different
    order of operations (online softmax over blocks of pages against one
    softmax over the gathered view; the kernel also rounds the
    probabilities to bf16 ahead of the second matmul), so they may land
    on neighbouring bf16 values: 2 units in the last place at the
    reference's largest magnitude, the TPU tier's rule."""
    if ref.dtype != jnp.bfloat16:
        return dict(rtol=2e-5, atol=2e-6)
    top = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
    return dict(rtol=0, atol=2 * 2.0 ** (np.floor(np.log2(top)) - 7))


# ----------------------------------------------------- raw kernel parity
@pytest.mark.parametrize("impl", ["lax", "pallas"])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_fused_matches_gather_oracle(impl, name):
    cfg = CONFIGS[name]
    q, pk, pv, block, qpos = _scenario(7, **cfg)
    ref = _oracle(q, pk, pv, block, qpos)
    out = paged_decode_attention(q, pk, pv, block, qpos, impl=impl,
                                 interpret=True)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **_tolerance(ref))


@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_all_padding_trash_row(impl):
    """A fresh slot (block table all trash-page-0, position 0) must stay
    finite and agree with the oracle — the engine pads every idle lane
    this way, so a NaN here poisons the whole running batch."""
    cfg = CONFIGS["gqa"]
    q, pk, pv, block, qpos = _scenario(11, **cfg, trash_row=True)
    assert int(block[0].max()) == 0 and int(qpos[0, 0]) == 0
    out = paged_decode_attention(q, pk, pv, block, qpos, impl=impl,
                                 interpret=True)
    ref = _oracle(q, pk, pv, block, qpos)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


SERVING_SHAPES = [        # b, t, hq, hkv, maxp: bf16, head_dim 128, page 16
    (16, 1, 8, 8, 8), (16, 1, 8, 2, 8), (1, 128, 8, 8, 8),
    # starcoder2-7b (G = 9): the decode step, prefill_512, and the
    # prefill_1024 that stacking all G x T rows in VMEM could not compile
    (32, 1, 36, 4, 36), (1, 512, 36, 4, 36), (1, 1024, 36, 4, 72),
    # starcoder2-3b (G = 12)
    (1, 512, 24, 2, 36), (1, 512, 24, 2, 72),
]


def _serving_args(b, t, hq, hkv, maxp, sharding=None):
    ps, d = 16, 128
    pool = jax.ShapeDtypeStruct((b * maxp + 1, hkv, ps, d), jnp.bfloat16,
                                sharding=sharding)
    return (jax.ShapeDtypeStruct((b, t, hq, d), jnp.bfloat16,
                                 sharding=sharding), pool, pool,
            jax.ShapeDtypeStruct((b, maxp), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=sharding))


@pytest.mark.parametrize("b,t,hq,hkv,maxp", SERVING_SHAPES)
def test_pallas_kernel_lowers_for_tpu_at_serving_shapes(b, t, hq, hkv,
                                                        maxp):
    """The TPU block-shape rules are checked when the kernel LOWERS, which
    needs no chip: cross-lower for the tpu platform at the serving shapes
    (bf16, head_dim 128, page 16).  The token-major pool layout failed
    exactly here ("last two dimensions of your block shape ...")."""
    fn = jax.jit(lambda *a: paged_decode_attention(
        *a, impl="pallas", interpret=False))
    with jax.enable_x64(False):   # as on the chip (the TPU tier has no x64)
        text = fn.trace(*_serving_args(b, t, hq, hkv, maxp)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "fused_paged_attention"') == 1


@pytest.mark.parametrize("b,t,hq,hkv,maxp", SERVING_SHAPES)
def test_tiling_at_serving_shapes(b, t, hq, hkv, maxp):
    """``paged_tiling`` is how the kernel says how it engaged: a block of
    8 pages of 16 (128 key positions), a row tile that is a whole number
    of bf16 sublane tiles (or all of a short ``t``), and buffers inside
    the budget whatever the group and the bucket."""
    ppb, tq, vmem = paged_tiling(b, t, hq, hkv, 128, 16, maxp,
                                 jnp.bfloat16)
    assert ppb == 8
    assert tq == t or (tq % 16 == 0 and t % tq == 0)
    assert vmem <= VMEM_BUDGET
    # the bound is what tiles the rows: one more doubling would pass it
    assert tq == t or paged_tiling(
        b, tq * 2, hq, hkv, 128, 16, maxp, jnp.bfloat16)[1] == tq


def test_the_mha_configs_take_the_heads_form():
    for name in ("mha_decode", "mha_decode_bf16"):
        cfg = CONFIGS[name]
        assert paged_form(cfg["t"], cfg["hq"], cfg["hkv"], cfg["page_size"],
                          cfg["maxp"]) == "heads", name
    assert {paged_form(c["t"], c["hq"], c["hkv"], c["page_size"], c["maxp"])
            for n, c in CONFIGS.items() if not n.startswith("mha_d")} == {
                "rows"}


def test_tiling_never_takes_more_pages_than_the_table_has():
    assert paged_tiling(4, 1, 8, 2, 128, 16, 3, jnp.bfloat16)[0] == 3
    assert paged_tiling(4, 1, 8, 2, 128, 64, 72, jnp.bfloat16)[0] == 2
    assert paged_tiling(4, 1, 8, 2, 128, 256, 8, jnp.bfloat16)[0] == 1


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a DESCRIBED v5e: the TPU's compiler is installed here
    and compiles for a chip that is not attached.  Made inside a fixture
    so that only the worker that runs this file loads the library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,t,hq,hkv,maxp", [
    (32, 1, 36, 4, 36), (1, 1024, 36, 4, 72), (1, 512, 24, 2, 72)])
def test_pallas_kernel_compiles_for_v5e(v5e_chip, b, t, hq, hkv, maxp):
    """Lowering does not see the VMEM a kernel takes; the chip's compiler
    does (``prefill_1024`` at G = 9 lowered fine and was refused there:
    31.02 MB against 16).  Compile for the described chip."""
    fn = jax.jit(lambda *a: paged_decode_attention(
        *a, impl="pallas", interpret=False))
    with jax.enable_x64(False):
        compiled = fn.lower(
            *_serving_args(b, t, hq, hkv, maxp, v5e_chip)).compile()
    assert "fused_paged_attention" in compiled.as_text()


@pytest.mark.parametrize("b,t,hq,maxp,window", [
    (32, 1, 72, 9, 512),       # a sliding layer's decode over its ring
    (32, 1, 48, 136, None),    # a full layer's decode, group of 6
    (1, 8192, 48, 136, None)])  # its largest prefill bucket
def test_laguna_shapes_compile_for_v5e(v5e_chip, b, t, hq, maxp, window):
    """``laguna-s-2.1-ep8``'s calls (8 kv heads of 128, pages of 64): the
    ring kernel's position recovery from scalar prefetch, and row tiles of
    a group of 6 at 8,192 positions, through the chip's compiler."""
    ps, hkv, d = 64, 8, 128
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    pool = sds((b * maxp + 1, hkv, ps, d), jnp.bfloat16)
    fn = jax.jit(lambda *a: paged_decode_attention(
        *a, window=window, impl="pallas", interpret=False))
    with jax.enable_x64(False):
        compiled = fn.lower(sds((b, t, hq, d), jnp.bfloat16), pool, pool,
                            sds((b, maxp), jnp.int32),
                            sds((b, t), jnp.int32)).compile()
    assert "fused_paged_attention" in compiled.as_text()


@pytest.mark.parametrize("t,d,hidden,count", [
    (64, 3584, 1024, 64),      # xing.serve-reason's decode step
    (48, 7168, 2048, 12),      # k2.serve-docqa's: the widest tiles
    (32, 3072, 1024, 32),      # laguna.serve-mixed-8k's
    (256, 7168, 2048, 12)])    # the most rows the layer streams
def test_grouped_experts_compiles_for_v5e(v5e_chip, t, d, hidden, count):
    """``helpers/grouped_experts.py`` at the expert cells' decode shapes
    through the chip's compiler (it refuses what lowering cannot see: the
    VMEM of a grid step's tiles), and the weight stacks reach the kernel as
    they are stored: no copy the size of one."""
    import re

    from deeplearning4j_tpu.helpers.grouped_experts import grouped_experts

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    bf = jnp.bfloat16
    fn = jax.jit(lambda *a: grouped_experts(*a, interpret=False))
    with jax.enable_x64(False):
        text = fn.lower(
            sds((t, d), bf), sds((count, d, hidden), bf),
            sds((count, d, hidden), bf), sds((count, hidden, d), bf),
            sds((t, count), jnp.float32), sds((count,), jnp.bool_)
        ).compile().as_text()
    assert "grouped_experts" in text
    copied = re.findall(r"= bf16\[([\d,]+)\][^ ]* copy\(", text)
    assert not [c for c in copied if c.startswith(f"{count},")]


@pytest.mark.parametrize("t,d,hidden,count,n,k", [
    (512, 3584, 1024, 64, 64, 4),      # xing.serve-reason's prefill_512
    (1024, 7168, 2048, 12, 384, 8),    # k2.serve-docqa's prefill_1024
    (1024, 3072, 1024, 32, 256, 10)])  # laguna.serve-mixed-8k's
def test_sorted_experts_compiles_for_v5e(v5e_chip, t, d, hidden, count, n,
                                         k):
    """The sorted path of a prefill bucket at the expert cells' widths
    through the chip's compiler (k2's four row tiles of f32 ``y`` beside
    its weight tiles are the most VMEM), the weight stacks read as they
    are stored."""
    import re

    from deeplearning4j_tpu.helpers.grouped_experts import sorted_experts

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    bf = jnp.bfloat16
    fn = jax.jit(lambda *a: sorted_experts(*a, n_experts=n, interpret=False))
    with jax.enable_x64(False):
        text = fn.lower(
            sds((t, d), bf), sds((count, d, hidden), bf),
            sds((count, d, hidden), bf), sds((count, hidden, d), bf),
            sds((t, k), jnp.int32), sds((t, k), jnp.float32)
        ).compile().as_text()
    assert "sorted_experts" in text
    copied = re.findall(r"= bf16\[([\d,]+)\][^ ]* copy\(", text)
    assert not [c for c in copied if c.startswith(f"{count},")]


# ------------------------------------------- the latent pool, read in place
# the two served latent shapes: (slots, heads, pages a slot) of
# xing.serve-reason and k2.serve-docqa; width 640, value 512, page 64
LATENT_SHAPES = {"xing": (64, 32, 48), "k2": (48, 64, 72)}


class _LatentSeam:
    """``PagedAttentionHelper.attend_latent`` held to one implementation
    (the seam picks by the backend: the lax loop here)."""

    def __init__(self, impl):
        self.impl = impl

    def attend_latent(self, q, pc, block, q_positions, *, v_width, scale):
        return paged_latent_attention(q, pc, block, q_positions,
                                      v_width=v_width, scale=scale,
                                      impl=self.impl, interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [32, 64])
@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_latent_pages_read_in_place_match_gather_and_absorbed(impl, heads,
                                                               dtype):
    """``LatentAttentionLayer._absorbed_paged`` (the kernel interpreted,
    and the lax page loop) against ``pool[block]`` + ``_absorbed`` at the
    served widths (640 / 512, page 64, groups of 32 and 64 rows): rows at
    position 0, at a block's last key, at the next block's first key, at
    the end of the table, inside a block; dead entries point at trash page
    0; the table (10 pages) is not whole blocks (8 pages of bf16, 4 of
    f32)."""
    from deeplearning4j_tpu.nn.layers.latent_attention import (
        LatentAttentionLayer)

    layer = LatentAttentionLayer(n_in=64, n_out=64, n_heads=heads, q_rank=16,
                                 kv_rank=512, nope_dim=128, rope_dim=64,
                                 v_dim=128)
    ps, maxp, pages, w = 64, 10, 30, 640
    ppb = paged_tiling(5, 1, heads, 1, w, ps, maxp, dtype, 512)[0]
    assert maxp % ppb and ppb * ps * w * jnp.dtype(dtype).itemsize \
        <= SLAB_BLOCK_BYTES
    qlast = np.array([0, ppb * ps - 1, ppb * ps, maxp * ps - 1, 100])
    b = len(qlast)
    rng = np.random.default_rng(heads)
    block = rng.integers(1, pages, size=(b, maxp))
    for i in range(b):
        block[i, qlast[i] // ps + 1:] = 0
    pool = rng.standard_normal((pages, ps, w))
    pool[..., 576:] = 0                       # the pool's padding columns
    pool = jnp.asarray(pool, dtype)
    params = {"Wkvb": jnp.asarray(
        0.05 * rng.standard_normal((512, heads * 256)), dtype)}
    q_nope = jnp.asarray(rng.standard_normal((b, 1, heads, 128)), dtype)
    q_rope = jnp.asarray(rng.standard_normal((b, 1, heads, 64)), dtype)
    block = jnp.asarray(block, jnp.int32)
    qpos = jnp.asarray(qlast[:, None], jnp.int32)
    want = layer._absorbed(params, q_nope, q_rope,
                           pool[block].reshape(b, -1, w), qpos)
    got = layer._absorbed_paged(params, q_nope, q_rope, pool, block, qpos,
                                _LatentSeam(impl))
    assert got.shape == want.shape == (b, 1, heads, 128)
    assert got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               **_tolerance(want))


@pytest.mark.parametrize("impl", ["lax", "pallas"])
def test_latent_all_trash_row_is_finite(impl):
    """An idle slot (position 0, every entry the trash page) attends to
    the one key at position 0 and nothing else."""
    rng = np.random.default_rng(5)
    pool = jnp.asarray(rng.standard_normal((6, 8, 128)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 128)), jnp.float32)
    block = jnp.asarray([[0, 0, 0], [3, 4, 0]], jnp.int32)
    got = paged_latent_attention(q, pool, block,
                                 jnp.asarray([[0], [12]], jnp.int32),
                                 v_width=32, scale=0.1, impl=impl,
                                 interpret=True)
    assert got.shape == (2, 1, 4, 32)
    np.testing.assert_allclose(
        np.asarray(got[0, 0]), np.broadcast_to(pool[0, 0, :32], (4, 32)),
        rtol=1e-6)


def test_latent_pool_of_another_shape_is_refused():
    z = jnp.zeros
    with pytest.raises(ValueError, match="latent pool"):
        paged_latent_attention(z((2, 1, 4, 128)), z((6, 8, 64)),
                               z((2, 3), jnp.int32), z((2, 1), jnp.int32),
                               v_width=32, scale=0.1)
    with pytest.raises(ValueError, match="q_positions"):
        paged_latent_attention(z((2, 1, 4, 128)), z((6, 8, 128)),
                               z((2, 3), jnp.int32), z((2, 2), jnp.int32),
                               v_width=32, scale=0.1)


@pytest.mark.parametrize("cell", sorted(LATENT_SHAPES))
def test_latent_tiling_sizes_a_block_by_its_bytes(cell):
    """One kv head: ``BLOCK_KEYS`` keys are 160 kB, a fifth of a GQA
    block, so the latent block is the pages ``SLAB_BLOCK_BYTES`` hold —
    from the shapes alone, the same at both served shapes."""
    b, h, maxp = LATENT_SHAPES[cell]
    ppb, tq, vmem = paged_tiling(b, 1, h, 1, 640, 64, maxp, jnp.bfloat16,
                                 512)
    assert ppb == SLAB_BLOCK_BYTES // (64 * 640 * 2) and tq == 1
    assert 128 < ppb * 64 and 2 * ppb * 64 * 640 * 2 < vmem <= VMEM_BUDGET
    # never under BLOCK_KEYS keys, never more than the table has
    assert paged_tiling(b, 1, h, 1, 640, 64, 2, jnp.bfloat16, 512)[0] == 2
    assert paged_tiling(b, 1, h, 1, 4096, 64, maxp, jnp.float32,
                        512)[0] == 2


# (b, t, hq, hkv, page, maxp) -> (pages a block, row tile, VMEM bytes) as
# the parent commit d0c4831 gives them: the GQA cells' calls (head 128,
# bf16) must not move when the latent pool gets a block of its own.  The
# last eight, as the parent commit 3147cf0 gives them, must not move when a
# group of one gets a form of its own: jamba2-3b's calls (20 heads over one
# kv head), olmo-hybrid-7b's prefills (30 over 30, 32 positions a tile),
# sc2-7b.serve-generate's
GQA_TILINGS = {
    (32, 1, 36, 4, 16, 36): (8, 1, 724992),      # sc2-7b.serve-complete
    (1, 256, 36, 4, 16, 36): (8, 64, 7749632),
    (1, 512, 36, 4, 16, 36): (8, 64, 7749632),
    (32, 1, 36, 4, 16, 72): (8, 1, 724992),      # sc2-7b.serve-complete-1k
    (1, 1024, 36, 4, 16, 72): (8, 64, 7749632),
    (32, 1, 48, 8, 64, 136): (2, 1, 1413120),    # laguna: a full layer
    (32, 1, 72, 8, 64, 9): (2, 1, 1413120),      # a ring of 9 pages
    (1, 1024, 48, 8, 64, 136): (2, 32, 5423104),
    (1, 8192, 48, 8, 64, 136): (2, 32, 5423104),
    (128, 1, 20, 1, 64, 24): (2, 1, 286720),     # jamba2.serve-chat
    (1, 256, 20, 1, 64, 24): (2, 64, 6356992),
    (1, 512, 20, 1, 64, 24): (2, 64, 6356992),
    (1, 1024, 20, 1, 64, 24): (2, 64, 6356992),
    (1, 256, 30, 30, 64, 24): (2, 32, 6463488),  # olmoh.serve-think
    (1, 512, 30, 30, 64, 24): (2, 32, 6463488),
    (16, 1, 36, 4, 16, 32): (8, 1, 724992),      # sc2-7b.serve-generate
    (1, 128, 36, 4, 16, 32): (8, 64, 7749632),
}
# olmoh.serve-think's decode step: 128 lanes, 30 heads over 30 kv heads of
# 128, pages of 64, 24 a lane -- a group of one row a kv head
OLMOH_DECODE = (128, 1, 30, 30, 64, 24)


@pytest.mark.parametrize("shape", sorted(GQA_TILINGS))
def test_gqa_tilings_are_what_they_were(shape):
    b, t, hq, hkv, ps, maxp = shape
    assert paged_tiling(b, t, hq, hkv, 128, ps, maxp,
                        jnp.bfloat16) == GQA_TILINGS[shape]
    assert paged_form(t, hq, hkv, ps, maxp) == "rows"


def test_the_form_is_heads_at_a_group_of_one_alone():
    """``paged_form`` picks ``heads`` for olmoh.serve-think's decode step
    and ``rows`` for every call above (its prefills, Jamba's group of 20
    over one kv head, the GQA cells'), a window's ring at a group of one,
    a latent pool, a chunk of multi-head attention, and a block that is
    not whole lanes."""
    b, t, hq, hkv, ps, maxp = OLMOH_DECODE
    assert paged_form(t, hq, hkv, ps, maxp) == "heads"
    ppb, tq, vmem = paged_tiling(b, t, hq, hkv, 128, ps, maxp, jnp.bfloat16)
    assert (ppb, tq) == (2, 1) and vmem <= VMEM_BUDGET
    assert paged_form(1, 30, 30, ps, 9, window=512) == "rows"
    assert paged_form(1, 30, 30, ps, maxp, v_width=512) == "rows"
    assert paged_form(2, 30, 30, ps, maxp) == "rows"
    assert paged_form(1, 30, 30, 16, 4) == "rows"        # 64 keys a block
    assert paged_form(1, 30, 30, 16, 8) == "heads"       # 128 keys


def test_the_path_follows_the_seam_and_the_backend(monkeypatch):
    """``paged_path``: the gather oracle where the seam gives way, the lax
    loop off the TPU, the kernel's form on it."""
    from deeplearning4j_tpu.helpers import paged_attention as pa

    shape = OLMOH_DECODE[1:]
    assert paged_path(*shape) == "lax"
    monkeypatch.setattr(pa, "default_impl", lambda: "pallas")
    assert paged_path(*shape) == "heads"
    assert paged_path(512, 30, 30, 64, 24) == "rows"
    assert _gather(lambda: paged_path(*shape)) == "gather"
    helpers.enable_helpers(False)
    try:
        assert paged_path(*shape) == "gather"
    finally:
        helpers.enable_helpers(True)
    assert set(PAGED_PATHS) == {"heads", "rows", "lax", "gather"}


def _kernel_text(fn, *args):
    """``fn`` lowered for the tpu platform, each Mosaic kernel's serialized
    body replaced by its module printed without source locations (the
    body carries the kernel's source lines, which any edit above it
    moves)."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    with jax.enable_x64(False):
        text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def body(m):
        with ctx, ir.Location.unknown():
            return ir.Module.parse(base64.b64decode(m.group(1))).operation \
                .get_asm(enable_debug_info=False)

    return re.sub(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", body, text)


# sha256 (first 16 hex) of ``_kernel_text`` of the kernel's call at each
# other cell's decode step and one prefill bucket, (b, t, hq, hkv, page,
# maxp, window), as the parent commit 3147cf0 lowers it: the ``rows`` form
# is the parent's kernel, instruction for instruction
PARENT_KERNELS = {
    (32, 1, 36, 4, 16, 36, None): "cb7c6269e60029f0",    # sc2-7b.serve-
    (1, 512, 36, 4, 16, 36, None): "1d381d49228f3577",   # complete
    (32, 1, 36, 4, 16, 72, None): "0ab46ce7f6dbc365",    # -1k
    (1, 1024, 36, 4, 16, 72, None): "9a8483cfd36dbf4f",
    (16, 1, 36, 4, 16, 32, None): "f0de2e5676ec5955",    # -generate
    (1, 128, 36, 4, 16, 32, None): "a71dbe7b6d4aaa88",
    (32, 1, 48, 8, 64, 136, None): "43b117fb776f0434",   # laguna
    (32, 1, 72, 8, 64, 9, 512): "ac54ec1a9ca0a6bd",
    (1, 8192, 48, 8, 64, 136, None): "4d74ad4c79bb36e9",
    (128, 1, 20, 1, 64, 24, None): "aafce970d677cb3e",   # jamba2
    (1, 1024, 20, 1, 64, 24, None): "a1fb28f62a1b52ec",
    (1, 512, 30, 30, 64, 24, None): "d5766e8477807cc5",  # olmoh's prefill
}
PARENT_LATENT_KERNELS = {"xing": "06736aaf2aac6689", "k2": "310c78d32d2cd584"}


@pytest.mark.parametrize("shape", list(PARENT_KERNELS), ids=str)
def test_the_rows_form_lowers_as_on_the_parent(shape):
    import hashlib

    b, t, hq, hkv, ps, maxp, window = shape
    sds = jax.ShapeDtypeStruct
    pool = sds((b * maxp + 1, hkv, ps, 128), jnp.bfloat16)
    fn = jax.jit(lambda *a: paged_decode_attention(
        *a, window=window, impl="pallas", interpret=False))
    text = _kernel_text(fn, sds((b, t, hq, 128), jnp.bfloat16), pool, pool,
                        sds((b, maxp), jnp.int32), sds((b, t), jnp.int32))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_KERNELS[shape]


@pytest.mark.parametrize("cell", sorted(PARENT_LATENT_KERNELS))
def test_the_latent_kernel_lowers_as_on_the_parent(cell):
    import hashlib

    text = _kernel_text(_latent_call, *_latent_args(cell))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_LATENT_KERNELS[cell]


def _olmoh_decode_args(sharding=None):
    b, t, hq, hkv, ps, maxp = OLMOH_DECODE
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    pool = sds((b * maxp + 1, hkv, ps, 128), jnp.bfloat16)
    return (sds((b, t, hq, 128), jnp.bfloat16), pool, pool,
            sds((b, maxp), jnp.int32), sds((b, t), jnp.int32))


def test_the_heads_form_lowers_for_tpu_once():
    """At olmoh.serve-think's decode shape the kernel is one
    ``fused_paged_attention`` call, its Mosaic module the ``heads`` form:
    one product for every head's keys where the ``rows`` form unrolls a
    body a kv head."""
    fn = jax.jit(lambda *a: paged_decode_attention(
        *a, impl="pallas", interpret=False))
    text = _kernel_text(fn, *_olmoh_decode_args())
    assert text.count('kernel_name = "fused_paged_attention"') == 1
    assert text.count("tpu.matmul") == 2


def _latent_args(cell, sharding=None):
    b, h, maxp = LATENT_SHAPES[cell]
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return (sds((b, 1, h, 640), jnp.bfloat16),
            sds((b * maxp + 1, 64, 640), jnp.bfloat16),
            sds((b, maxp), jnp.int32), sds((b, 1), jnp.int32))


_latent_call = jax.jit(lambda *a: paged_latent_attention(
    *a, v_width=512, scale=0.0722, impl="pallas", interpret=False))


@pytest.mark.parametrize("cell", sorted(LATENT_SHAPES))
def test_latent_kernel_lowers_for_tpu_at_serving_shapes(cell):
    with jax.enable_x64(False):
        text = _latent_call.trace(*_latent_args(cell)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "latent_paged_attention"') == 1
    assert "fused_paged_attention" not in text


@pytest.mark.parametrize("cell", sorted(LATENT_SHAPES))
def test_latent_kernel_compiles_for_v5e(v5e_chip, cell):
    """The two served latent shapes through the chip's compiler: the
    lane-aligned slice of the key slab that is the value, 0.66 MB copy
    sets in two slots, the VMEM the kernel takes."""
    with jax.enable_x64(False):
        compiled = _latent_call.lower(*_latent_args(cell, v5e_chip)).compile()
    assert "latent_paged_attention" in compiled.as_text()


@pytest.mark.parametrize("n", [4, 40])   # a decode step's rows, a chunk
def test_rows_of_a_table_write_what_the_slab_scatter_writes(n):
    """``write_token_rows`` against ``pool.at[page, :, off].set``, trash
    page hit twice and a cast to the pool's dtype among the rows."""
    rng = np.random.default_rng(n)
    pool = jnp.asarray(rng.standard_normal((7, 4, 16, 8)), jnp.bfloat16)
    flat = rng.permutation(6 * 16)[:n - 2] + 16     # distinct (page, off)
    page = jnp.asarray(np.r_[flat // 16, 0, 0], jnp.int32)
    off = jnp.asarray(np.r_[flat % 16, 3, 9], jnp.int32)
    rows = jnp.asarray(rng.standard_normal((n, 4, 8)), jnp.float32)
    got = write_token_rows(pool, page, off, rows)
    want = pool.at[page, :, off].set(rows.astype(pool.dtype))
    assert got.dtype == pool.dtype and (got == want).all()


def test_compiled_kernel_rejects_page_size_the_dtype_cannot_tile():
    pool = jnp.zeros((5, 2, 8, 128), jnp.bfloat16)   # bf16 tiles 16 rows
    with pytest.raises(ValueError, match="page_size=8"):
        paged_decode_attention(
            jnp.zeros((1, 1, 2, 128), jnp.bfloat16), pool, pool,
            jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 1), jnp.int32),
            impl="pallas", interpret=False)


def test_mode_toggle_and_helper_gating():
    """The seam offers ``PagedAttentionHelper`` by default and withholds it
    with helpers disabled: the one way back to the gather."""
    assert isinstance(helpers.get_helper("paged_attention"),
                      PagedAttentionHelper)
    helpers.enable_helpers(False)
    try:
        assert helpers.get_helper("paged_attention") is None
    finally:
        helpers.enable_helpers(True)


def test_latent_path_follows_the_seam_and_the_dtype():
    """``LatentAttentionLayer.path``: the kernel for a single token while
    the seam offers it; the gather where the seam withholds the helper,
    with helpers disabled, in float64, and on a TPU for a page the dtype
    cannot tile; a chunk never takes it."""
    from deeplearning4j_tpu.helpers import paged_attention as pa
    from deeplearning4j_tpu.nn.layers.latent_attention import (
        LATENT_PATHS, LatentAttentionLayer, latent_path)

    layer = LatentAttentionLayer(n_in=8, n_heads=2, q_rank=4, kv_rank=8,
                                 nope_dim=4, rope_dim=2, v_dim=4)
    assert layer.path(1, False, 8, jnp.float32) == "paged"
    assert layer.path(1, False, 8, jnp.bfloat16) == "paged"
    assert layer.path(1, False, 8, jnp.float64) == "gathered"
    assert _gather(lambda: layer.path(1, False, 8, jnp.float32)) == "gathered"
    helpers.enable_helpers(False)
    try:
        assert layer.path(1, False, 8, jnp.float32) == "gathered"
    finally:
        helpers.enable_helpers(True)
    assert layer.path(16, True, 8, jnp.float32) == "expanded"
    assert layer.path(16, False, 8, jnp.float32) == "gathered"
    assert {latent_path(t, z, k) for t in (1, 16) for z in (False, True)
            for k in (False, True)} == set(LATENT_PATHS)
    seam = PagedAttentionHelper()
    assert seam.supports_latent(128, 8, jnp.bfloat16)      # the lax loop
    orig, pa.default_impl = pa.default_impl, lambda: "pallas"
    try:
        assert not seam.supports_latent(128, 8, jnp.bfloat16)
        assert seam.supports_latent(640, 64, jnp.bfloat16)
    finally:
        pa.default_impl = orig


def test_lax_fallback_zero_recompiles_across_fill_levels():
    """The fori_loop fallback bounds its page walk by a TRACED watermark
    (max position), so rows filling up over decode steps must not force
    retraces — the engine's zero-steady-state-compile contract depends
    on it."""
    cfg = CONFIGS["gqa"]
    ps = cfg["page_size"]
    fn = jax.jit(lambda *a: paged_decode_attention(*a, impl="lax"))
    q, pk, pv, block, qpos = _scenario(13, **cfg)
    fn(q, pk, pv, block, qpos).block_until_ready()
    traces = 0
    for fill in (0, ps - 1, 2 * ps, 3 * ps + 1):
        qp = jnp.full_like(qpos, fill)
        with jax.log_compiles(False):
            before = fn._cache_size()
            fn(q, pk, pv, block, qp).block_until_ready()
            traces += fn._cache_size() - before
    assert traces == 0


# ------------------------------------------------- layer-level streaming
def test_row_crosses_page_boundary_mid_decode():
    """Token-by-token streaming through ``apply_with_carry``: the row's
    position walks across page boundaries (ps-1 -> ps allocates the next
    page's lane); every step's fused output must match the gather
    oracle's, including the boundary steps."""
    ps, maxp, num_pages = 4, 3, 7
    layer = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4, causal=True,
                               n_kv_heads=2)
    params = layer.init(jax.random.PRNGKey(0))
    steps = 2 * ps + 2                             # crosses two boundaries
    xs = jax.random.normal(jax.random.PRNGKey(1), (steps, 1, 1, 32))
    block = jnp.asarray([[1, 4, 2]], jnp.int32)    # page ids, row 0

    def run():
        carry = dict(layer.init_paged_cache(num_pages, ps),
                     block=block, pos=jnp.zeros((1,), jnp.int32))
        outs = []
        for i in range(steps):
            y, _, nc = layer.apply_with_carry(params, {}, xs[i], carry)
            outs.append(y)
            carry = dict(nc, block=block)
        return outs

    fused = run()
    oracle = _gather(run)
    for i, (a, b) in enumerate(zip(fused, oracle)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6,
            err_msg=f"step {i} (position {i}, page {i // ps})")


# ------------------------------------------------- engine-level oracles
def _small_lm(seed=12345):
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    return transformer_char_lm(vocab_size=VOCAB, d_model=32, n_heads=4,
                               layers=2, max_cache=128, seed=seed)


def _engine(lm, **kw):
    from deeplearning4j_tpu.generation import GenerationEngine

    return GenerationEngine(lm, slots=4, page_size=4, max_context=32,
                            max_queue=64, deadline_s=60.0, **kw).start()


def _gather(fn):
    """``fn()`` with the seam withholding the paged-attention helper alone:
    the layers take the gather oracle, every other helper as it was."""
    get = helpers.get_helper
    helpers.get_helper = lambda kind: (None if kind == "paged_attention"
                                       else get(kind))
    try:
        return fn()
    finally:
        helpers.get_helper = get


def test_programs_log_their_tiling_once_a_program(monkeypatch, caplog):
    """``GenerationPrograms.warm`` says how the kernel engaged in each
    compute program (static per program, so one line a program is the
    whole account) — and says nothing where the lax loop runs."""
    import logging

    from deeplearning4j_tpu.generation.programs import GenerationPrograms
    from deeplearning4j_tpu.helpers import paged_attention as pa

    progs = GenerationPrograms(_small_lm(), slots=4, pages_per_slot=8,
                               page_size=4, num_pages=33,
                               prefill_buckets=(8, 16))

    def lines():
        caplog.clear()
        with caplog.at_level(logging.INFO,
                             logger="deeplearning4j_tpu.generation"):
            progs._log_tiling()
        return [r.getMessage() for r in caplog.records
                if "fused_paged_attention" in r.getMessage()]

    assert lines() == []                      # CPU: impl "lax"
    monkeypatch.setattr(pa, "default_impl", lambda: "pallas")
    said = lines()
    assert [m.split(":")[0] for m in said] == [
        "generation.prefill_8", "generation.prefill_16",
        "generation.decode"]
    ppb, tq, _ = paged_tiling(4, 1, 4, 4, 8, 4, 8, jnp.float32)
    assert f"{ppb} pages a block, {tq} query positions a tile" in said[-1]


def test_engine_join_leave_parity_fused_vs_gather(rng):
    """The PR-13 scheduler oracle, run cross-mode: mixed join/leave
    traffic on the fused default must produce the same greedy tokens as
    the gather-oracle engine decoding the same requests sequentially."""
    import time

    lm = _small_lm()
    prompts = [rng.randint(0, VOCAB, rng.randint(1, 12)).tolist()
               for _ in range(8)]
    lens = [int(rng.randint(2, 10)) for _ in prompts]

    def gather_sequential():
        eng = _engine(lm)
        try:
            return [eng.generate(p, n).tolist()
                    for p, n in zip(prompts, lens)]
        finally:
            eng.stop()

    ref = _gather(gather_sequential)

    eng = _engine(lm)            # fused default, concurrent + staggered
    try:
        handles = []
        for i, (p, n) in enumerate(zip(prompts, lens)):
            handles.append(eng.submit(p, n))
            if i % 3 == 0:
                time.sleep(0.002)
        mixed = [h.result(timeout=60) for h in handles]
    finally:
        eng.stop()
    assert mixed == ref


def test_engine_prefix_cache_hit_parity(rng):
    """A persistent prefix-cache hit restores cached KV pages the fused
    kernel then attends over — the suffix decoded off restored pages
    must match the gather oracle's."""
    lm = _small_lm()
    prefix = rng.randint(0, VOCAB, 12).tolist()
    tails = [rng.randint(0, VOCAB, 3).tolist() for _ in range(2)]

    def run():
        eng = _engine(lm, prefix_cache=True)
        try:
            out, shared = [], []
            for tail in tails:
                h = eng.submit(prefix + tail, 6)
                out.append(h.result(timeout=60))
                shared.append(h.shared_len)
            return out, shared
        finally:
            eng.stop()

    fused_out, fused_shared = run()
    gather_out, gather_shared = _gather(run)
    assert fused_shared[1] > 0 and gather_shared[1] > 0   # hit path ran
    assert fused_out == gather_out


def test_engine_hot_swap_parity(rng):
    """The hot-swap drill cross-mode: greedy outputs before AND after a
    between-requests weight swap must agree between the fused default
    and the gather oracle."""
    prompt = rng.randint(0, VOCAB, 6).tolist()

    def run():
        eng = _engine(_small_lm())
        try:
            pre = eng.generate(prompt, 8).tolist()
            eng.deploy("default", _small_lm(seed=777))
            post = eng.generate(prompt, 8).tolist()
            return pre, post
        finally:
            eng.stop()

    fused = run()
    oracle = _gather(run)
    assert fused == oracle
    assert fused[0] != fused[1]       # the swap actually changed weights


def test_engine_serves_mha_through_the_heads_form(rng, monkeypatch):
    """Multi-head attention (4 heads over 4 kv heads) with the seam made to
    take the Pallas path (interpreted off the chip), pages of 16 in a
    128-position context: a decode step takes the ``heads`` form (a block
    of 8 pages, 128 keys), a prefill the ``rows`` form.  The served tokens
    are the gather oracle's, and ``dl4j_layer_path_steps_total`` of kind
    ``attention`` reads ``heads`` once a dispatched decode step, ``rows``
    once a prefill."""
    from deeplearning4j_tpu.generation import GenerationEngine
    from deeplearning4j_tpu.helpers import paged_attention as pa
    from deeplearning4j_tpu.observability.metrics import MetricsRegistry

    lm = _small_lm()
    prompts = [rng.randint(0, VOCAB, rng.randint(1, 12)).tolist()
               for _ in range(5)]
    lens = [int(rng.randint(2, 10)) for _ in prompts]

    def run():
        eng = GenerationEngine(lm, slots=4, page_size=16, max_context=128,
                               max_queue=64, deadline_s=120.0,
                               registry=MetricsRegistry()).start()
        try:
            handles = [eng.submit(p, n) for p, n in zip(prompts, lens)]
            return eng, [h.result(timeout=300) for h in handles]
        finally:
            eng.stop()

    oracle, want = _gather(run)
    monkeypatch.setattr(pa, "default_impl", lambda: "pallas")
    eng, got = run()
    assert got == want
    steps = lambda e, **kw: e.metrics.registry.get_value(
        "dl4j_layer_path_steps_total", kind="attention", **kw)
    dispatched = sum(eng.metrics.registry.get_value(
        "dl4j_decode_dispatch_total", mode=m) or 0 for m in ("ahead", "sync"))
    assert steps(eng, stage="decode", path="heads") == dispatched > 0
    assert steps(eng, stage="prefill", path="rows") == len(prompts)
    assert steps(eng, stage="decode", path="rows") is None
    assert steps(oracle, stage="prefill", path="gather") == len(prompts)


# ------------------------------------------------------- fused epilogue
def _np_ref(h, res, gamma, beta, eps, mask, keep):
    x = np.asarray(h, np.float64)
    if res is not None:
        x = x + np.asarray(res, np.float64)
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    y = ((x - mu) / np.sqrt(var + eps) * np.asarray(gamma, np.float64)
         + np.asarray(beta, np.float64))
    if mask is not None:
        y = np.where(np.asarray(mask), y / keep, 0.0)
    return y


@pytest.mark.parametrize("variant",
                         ["residual_dropout", "prologue", "norm_only"])
def test_epilogue_forward_parity(variant):
    rng = np.random.default_rng(21)
    m, c = 17, 40                                   # pad-heavy odd shape
    h = jnp.asarray(rng.standard_normal((m, c)), jnp.float32)
    gamma = jnp.asarray(rng.standard_normal(c), jnp.float32)
    beta = jnp.asarray(rng.standard_normal(c), jnp.float32)
    res = (jnp.asarray(rng.standard_normal((m, c)), jnp.float32)
           if variant == "residual_dropout" else None)
    mask, keep, rate = None, 1.0, 0.0
    if variant != "norm_only":
        keep, rate = 0.75, 0.25
        mask = jnp.asarray(rng.random((m, c)) < keep)
    out = dropout_residual_norm(h, res, gamma, beta, eps=1e-5, rate=rate,
                                mask=mask)
    ref = _np_ref(h, res, gamma, beta, 1e-5,
                  np.asarray(mask) if mask is not None else None, keep)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_epilogue_grads_match_reference():
    rng = np.random.default_rng(22)
    m, c = 12, 96
    h = jnp.asarray(rng.standard_normal((m, c)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((m, c)), jnp.float32)
    gamma = jnp.asarray(rng.standard_normal(c), jnp.float32)
    beta = jnp.asarray(rng.standard_normal(c), jnp.float32)
    mask = jnp.asarray(rng.random((m, c)) < 0.8)

    def fused(h, res, gamma, beta):
        return jnp.sum(jnp.sin(dropout_residual_norm(
            h, res, gamma, beta, eps=1e-5, rate=0.2, mask=mask)))

    def ref(h, res, gamma, beta):
        x = h + res
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + 1e-5) * gamma + beta
        y = jnp.where(mask, y / 0.8, 0.0)
        return jnp.sum(jnp.sin(y))

    gf = jax.grad(fused, argnums=(0, 1, 2, 3))(h, res, gamma, beta)
    gr = jax.grad(ref, argnums=(0, 1, 2, 3))(h, res, gamma, beta)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_epilogue_mask_bit_identical_to_maybe_dropout():
    """Same rng key => the fused prologue's keep/drop pattern is the
    SAME bernoulli draw ``Layer.maybe_dropout`` makes — the fused and
    unfused train paths see identical masks, not just same-rate ones."""
    from deeplearning4j_tpu.nn.layers.dense import DenseLayer

    rng_key = jax.random.PRNGKey(99)
    x = jax.random.normal(jax.random.PRNGKey(5), (9, 64), jnp.float32)
    gamma, beta = jnp.ones((64,)), jnp.zeros((64,))
    out = dropout_residual_norm(x, None, gamma, beta, eps=1e-5, rate=0.4,
                                rng=rng_key, train=True)
    layer = DenseLayer(n_in=64, n_out=64, dropout=0.4)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    ln = (x - mu) * jax.lax.rsqrt(var + 1e-5)
    ref = layer.maybe_dropout(ln, train=True, rng=rng_key)
    assert bool(jnp.array_equal(out == 0.0, ref == 0.0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_epilogue_supports_gating():
    h = FusedEpilogueHelper()                       # allow_interpret off
    x = jnp.zeros((8, 64), jnp.float32)
    assert not h.supports(x)                        # CPU: stock jnp path
    h = FusedEpilogueHelper(allow_interpret=True)
    assert h.supports(x)
    assert not h.supports(jnp.zeros((8, 64), jnp.float64))
    assert not h.supports(jnp.zeros((9000, 1000), jnp.float32))


def test_residual_block_fused_parity_and_remat_grads():
    """ResidualBlock routes its leading LayerNorm + the next sublayer's
    input dropout through the fused prologue when the helper qualifies;
    fused and stock paths must agree forward (train + eval) and through
    remat gradients."""
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.composite import ResidualBlock
    from deeplearning4j_tpu.nn.layers.dense import DenseLayer
    from deeplearning4j_tpu.nn.layers.normalization import LayerNorm

    blk = ResidualBlock(layers=(
        LayerNorm(), DenseLayer(n_out=64, activation="relu", dropout=0.3),
        DenseLayer(n_out=64)), remat=True).setup(InputType.feed_forward(64))
    params = blk.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64), jnp.float32)
    rng_key = jax.random.PRNGKey(2)

    def run(train):
        y, _ = blk.apply(params, {}, x, train=train,
                         rng=rng_key if train else None)
        return y

    def grads():
        def loss(p):
            y, _ = blk.apply(p, {}, x, train=True, rng=rng_key)
            return jnp.sum(y * y)
        return jax.grad(loss)(params)

    ref_train, ref_eval, ref_g = run(True), run(False), grads()
    saved = helpers._registry.get("epilogue")
    helpers._registry["epilogue"] = FusedEpilogueHelper(
        allow_interpret=True)
    try:
        fused_train, fused_eval, fused_g = run(True), run(False), grads()
    finally:
        if saved is None:
            helpers._registry.pop("epilogue", None)
        else:
            helpers._registry["epilogue"] = saved
    np.testing.assert_allclose(np.asarray(fused_train),
                               np.asarray(ref_train), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(fused_eval),
                               np.asarray(ref_eval), rtol=2e-5, atol=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(fused_g),
                    jax.tree_util.tree_leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# --------------------------------------------------------- harness gates
def test_trust_registry_gate_green_on_committed_doc():
    from deeplearning4j_tpu.observability.kerneldiff import check_registry

    path = os.path.join(os.path.dirname(__file__), "..",
                        "kernel_trust.json")
    assert check_registry(path) == 0


def test_trust_registry_gate_flags_mismatch(tmp_path):
    import json

    doc = {"kernels": {"flash_attention": {}, "ghost_kernel": {}}}
    p = tmp_path / "trust.json"
    p.write_text(json.dumps(doc))
    from deeplearning4j_tpu.observability.kerneldiff import check_registry

    assert check_registry(str(p)) == 1


@pytest.mark.parametrize("b,t", [(128, 1), (1, 1024)])
def test_jamba_shapes_compile_for_v5e(v5e_chip, b, t):
    """``jamba2-3b``'s attention calls (20 query heads over ONE kv head of
    128, pages of 64, 24 a slot): a group of 20 rows a kv head, against 6,
    9 and 12 in the older cells, through the chip's compiler."""
    ps, hq, hkv, d, maxp = 64, 20, 1, 128, 24
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    pool = sds((128 * maxp + 1, hkv, ps, d), jnp.bfloat16)
    fn = jax.jit(lambda *a: paged_decode_attention(
        *a, impl="pallas", interpret=False))
    with jax.enable_x64(False):
        compiled = fn.lower(sds((b, t, hq, d), jnp.bfloat16), pool, pool,
                            sds((b, maxp), jnp.int32),
                            sds((b, t), jnp.int32)).compile()
    assert "fused_paged_attention" in compiled.as_text()
    ppb, tq, vmem = paged_tiling(b, t, hq, hkv, d, ps, maxp, jnp.bfloat16)
    assert vmem <= VMEM_BUDGET and (tq == t or t % tq == 0)


@pytest.mark.parametrize("lanes,t", [(128, 1), (1, 1024)])
def test_the_state_slots_are_stepped_in_place_on_v5e(v5e_chip, lanes, t):
    """``MambaLayer`` at ``jamba2-3b``'s widths over its state slots,
    compiled for the described chip with the pools donated: the decode step
    (128 lanes) is one pass over the pool where it lies, a prefill bucket
    a scatter of one row — neither re-lays the float32 pool out (a
    pool-sized ``copy`` of ``sh`` would cost 42 MB a layer a step)."""
    import re

    from deeplearning4j_tpu.nn.layers import MambaLayer

    layer = MambaLayer(n_in=2560, n_out=2560, dt_rank=160, name="m")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    with jax.enable_x64(False):
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, jnp.bfloat16),
            jax.eval_shape(layer.init, jax.random.PRNGKey(0)))
        pool = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), jax.eval_shape(
                lambda: layer.init_paged_cache(2, 64, jnp.bfloat16,
                                               state_slots=128)))

        def run(p, u, pool, where, pos):
            carry = {**pool, "pos": pos,
                     **({"lanes": where > 0} if t == 1 else
                        {"rows": where, "live": pos + t - 5})}
            y, _, new = layer.apply_with_carry(p, {}, u, carry)
            return y, {k: new[k] for k in pool}

        text = jax.jit(run, donate_argnums=(2,)).lower(
            params, sds((lanes, t, 2560), jnp.bfloat16), pool,
            sds((lanes,), jnp.int32), sds((lanes,), jnp.int32)
        ).compile().as_text()
    copies = re.findall(r"= f32\[129,16,5120\][^ ]* copy\(", text)
    assert not copies, copies
    assert ("while(" in text) == (t > 1)        # the chunked scan's loop


@pytest.mark.parametrize("b,t", [(128, 1), (1, 512)])
def test_olmo_hybrid_shapes_compile_for_v5e(v5e_chip, b, t):
    """``olmo-hybrid-7b-pp4``'s full layers: 30 query heads over 30 kv heads
    of 128 (a group of ONE row a kv head, plain multi-head attention),
    pages of 64, 24 a slot, through the chip's compiler: the decode step in
    the ``heads`` form, a prefill bucket in the ``rows`` form, both within
    ``VMEM_BUDGET``."""
    ps, hq, hkv, d, maxp = 64, 30, 30, 128, 24
    assert paged_form(t, hq, hkv, ps, maxp) == ("heads" if t == 1
                                                else "rows")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    pool = sds((128 * maxp + 1, hkv, ps, d), jnp.bfloat16)
    fn = jax.jit(lambda *a: paged_decode_attention(
        *a, impl="pallas", interpret=False))
    with jax.enable_x64(False):
        compiled = fn.lower(sds((b, t, hq, d), jnp.bfloat16), pool, pool,
                            sds((b, maxp), jnp.int32),
                            sds((b, t), jnp.int32)).compile()
    assert "fused_paged_attention" in compiled.as_text()
    ppb, tq, vmem = paged_tiling(b, t, hq, hkv, d, ps, maxp, jnp.bfloat16)
    assert vmem <= VMEM_BUDGET and (tq == t or t % tq == 0)


@pytest.mark.parametrize("lanes,t", [(128, 1), (1, 512)])
def test_the_delta_rule_slots_are_stepped_in_place_on_v5e(v5e_chip, lanes, t):
    """``GatedDeltaNetLayer`` at ``olmo-hybrid-7b-pp4``'s widths over its
    state slots, compiled for the described chip with the pools donated:
    the slot layout [15, 96, 384] is whole tiles, and neither the decode
    step (128 lanes: two passes over the pool where it lies, the second
    writing in place) nor a prefill bucket (one row scattered back) makes a
    pool-sized ``copy`` of ``sh`` (283 MB a layer a step) or a gather of
    the state rows."""
    import re

    from deeplearning4j_tpu.nn.layers import GatedDeltaNetLayer

    layer = GatedDeltaNetLayer(n_in=3840, n_out=3840, n_heads=30, d_k=96,
                               d_v=192, allow_neg_eigval=True, name="g")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    with jax.enable_x64(False):
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, jnp.bfloat16),
            jax.eval_shape(layer.init, jax.random.PRNGKey(0)))
        pool = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), jax.eval_shape(
                lambda: layer.init_paged_cache(2, 64, jnp.bfloat16,
                                               state_slots=128)))

        def run(p, u, pool, where, pos):
            carry = {**pool, "pos": pos,
                     **({"lanes": where > 0} if t == 1 else
                        {"rows": where, "live": pos + t - 5})}
            y, _, new = layer.apply_with_carry(p, {}, u, carry)
            return y, {k: new[k] for k in pool}

        text = jax.jit(run, donate_argnums=(2,)).lower(
            params, sds((lanes, t, 3840), jnp.bfloat16), pool,
            sds((lanes,), jnp.int32), sds((lanes,), jnp.int32)
        ).compile().as_text()
    state = r"f32\[(129|128),15,96,384\][^ ]* "
    assert not re.findall(state + r"(copy|gather|scatter)\(", text)
    assert re.findall(r"= f32\[129,15,96,384\][^ ]* dynamic-update-slice\(",
                      text)
    # the chunked form's solve (a prefill) is the chip's own expansion
    assert ("InvertDiagBlocksLowerTriangular" in text) == (t > 1)


def test_the_delta_rule_kernel_steps_the_slots_in_place_on_v5e(v5e_chip,
                                                               monkeypatch):
    """The same decode step where the seam offers the kernel (as on the
    chip): one ``delta_state_step`` call a layer, the pool aliased to its
    result — no pool-sized ``copy``, gather, scatter or update of ``sh``
    beside it, so each row is read once and written once."""
    import re

    from deeplearning4j_tpu.helpers import delta_rule
    from deeplearning4j_tpu.nn.layers import GatedDeltaNetLayer

    monkeypatch.setattr(delta_rule, "_interpret", lambda: False)
    layer = GatedDeltaNetLayer(n_in=3840, n_out=3840, n_heads=30, d_k=96,
                               d_v=192, allow_neg_eigval=True, name="g")
    assert layer.path(1) == "delta_kernel"
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    with jax.enable_x64(False):
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, jnp.bfloat16),
            jax.eval_shape(layer.init, jax.random.PRNGKey(0)))
        pool = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), jax.eval_shape(
                lambda: layer.init_paged_cache(2, 64, jnp.bfloat16,
                                               state_slots=128)))

        def run(p, u, pool, lanes, pos):
            carry = {**pool, "pos": pos, "lanes": lanes > 0}
            y, _, new = layer.apply_with_carry(p, {}, u, carry)
            return y, {k: new[k] for k in pool}

        compiled = jax.jit(run, donate_argnums=(2,)).lower(
            params, sds((128, 1, 3840), jnp.bfloat16), pool,
            sds((128,), jnp.int32), sds((128,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(r"= \(f32\[129,15,96,384\][^=]*custom-call\(", text)
    assert len(calls) == 1 and "delta_state_step" in text
    state = r"f32\[(129|128),15,96,384\][^ ]* "
    assert not re.findall(
        state + r"(copy|gather|scatter|dynamic-update-slice|fusion)\(", text)
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 129 * 2_211_840


def test_the_kda_kernel_steps_the_slots_in_place_on_v5e(v5e_chip,
                                                        monkeypatch):
    """``KimiDeltaAttentionLayer`` at ``ling-3.0-flash-ep8``'s widths (32
    heads x [128, 128], one head a row of whole lanes) over 256 state
    slots, compiled for the described chip with the pools donated: one
    ``kda_state_step`` call, the pool aliased to its result, no pool-sized
    ``copy``, gather, scatter or update of ``sh`` beside it."""
    import re

    from deeplearning4j_tpu.helpers import delta_rule
    from deeplearning4j_tpu.nn.layers import KimiDeltaAttentionLayer

    monkeypatch.setattr(delta_rule, "_interpret", lambda: False)
    layer = KimiDeltaAttentionLayer(n_in=2560, n_out=2560, n_heads=32,
                                    d_k=128, d_v=128, name="k")
    assert layer.path(1) == "kda_kernel"
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    with jax.enable_x64(False):
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, jnp.bfloat16),
            jax.eval_shape(layer.init, jax.random.PRNGKey(0)))
        pool = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), jax.eval_shape(
                lambda: layer.init_paged_cache(2, 64, jnp.bfloat16,
                                               state_slots=256)))

        def run(p, u, pool, lanes, pos):
            carry = {**pool, "pos": pos, "lanes": lanes > 0}
            y, _, new = layer.apply_with_carry(p, {}, u, carry)
            return y, {k: new[k] for k in pool}

        compiled = jax.jit(run, donate_argnums=(2,)).lower(
            params, sds((256, 1, 2560), jnp.bfloat16), pool,
            sds((256,), jnp.int32), sds((256,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(r"= \(f32\[257,32,128,128\][^=]*custom-call\(", text)
    assert len(calls) == 1 and "kda_state_step" in text
    state = r"f32\[(257|256),32,128,128\][^ ]* "
    assert not re.findall(
        state + r"(copy|gather|scatter|dynamic-update-slice|fusion)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 257 * 2_097_152


@pytest.mark.parametrize("t", [1024, 4096])
def test_the_kda_chunk_kernel_runs_a_prefill_on_v5e(v5e_chip, monkeypatch,
                                                    t):
    """A prefill bucket of ``KimiDeltaAttentionLayer`` at
    ``ling-3.0-flash-ep8``'s widths where the seam offers its kernels (as
    on the chip), compiled for the described chip: the chunked rule is ONE
    ``kda_chunk`` call (the ``kda_chunk_kernel`` path), the chip's
    triangular solve and the ``lax`` form's loop over the chunks are gone,
    and the state pool is neither copied nor gathered whole."""
    import re

    from deeplearning4j_tpu.helpers import delta_rule
    from deeplearning4j_tpu.nn.layers import KimiDeltaAttentionLayer

    monkeypatch.setattr(delta_rule, "_interpret", lambda: False)
    layer = KimiDeltaAttentionLayer(n_in=2560, n_out=2560, n_heads=32,
                                    d_k=128, d_v=128, name="k")
    assert layer.path(t) == "kda_chunk_kernel"
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)
    with jax.enable_x64(False):
        params = jax.tree_util.tree_map(
            lambda a: sds(a.shape, jnp.bfloat16),
            jax.eval_shape(layer.init, jax.random.PRNGKey(0)))
        pool = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), jax.eval_shape(
                lambda: layer.init_paged_cache(2, 64, jnp.bfloat16,
                                               state_slots=256)))

        def run(p, u, pool, rows, pos):
            carry = {**pool, "pos": pos, "rows": rows, "live": pos + t - 5}
            y, _, new = layer.apply_with_carry(p, {}, u, carry)
            return y, {k: new[k] for k in pool}

        compiled = jax.jit(run, donate_argnums=(2,)).lower(
            params, sds((1, t, 2560), jnp.bfloat16), pool,
            sds((1,), jnp.int32), sds((1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"custom-call\([^\n]*kda_chunk", text)) == 1
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert "while(" not in text
    state = r"f32\[(257|256),32,128,128\][^ ]* "
    assert not re.findall(state + r"(copy|gather)\(", text)
