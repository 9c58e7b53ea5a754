"""The path a layer reports is the path its traced branch runs.

``GenerationPrograms.paths`` holds, for each compute program, the
``(kind, path)`` pairs its layers' ``serving_path`` names — the host's
account, which the engine counts as ``dl4j_layer_path_steps_total``.  Each
case here builds a toy net's programs, runs one of them on the device with
a spy on the function that implements every path of the layer kind under
test (a ``jax.debug.callback``, so a branch of a ``lax.cond`` the device did
not take says nothing), and checks that what ran is what the table names:
with the helper seam on, and with one helper withheld.
"""

import functools

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.generation.programs import GenerationPrograms
from deeplearning4j_tpu.helpers import delta_rule as dr
from deeplearning4j_tpu.helpers import paged_attention as pa
from deeplearning4j_tpu.helpers import selective_scan as ss
from deeplearning4j_tpu.helpers.grouped_experts import GroupedExpertsHelper
from deeplearning4j_tpu.nn.layers import attention
from deeplearning4j_tpu.nn.layers.latent_attention import LatentAttentionLayer
from deeplearning4j_tpu.nn.layers.moe import RoutedMoELayer

pytestmark = pytest.mark.generation

# the function that does each path's work, (owner, attribute), by the
# family of layers whose paths they are; the ``heads`` form runs inside
# the kernel's entry, which the ``rows`` form is alone
SPIES = {
    "self-attention": {
        "heads": (pa, "_pallas_heads"),
        "rows": (pa, "_pallas_paged"),
        "lax": (pa, "_lax_paged"),
        "gather": (attention, "paged_attention")},
    "latent attention": {
        "paged": (LatentAttentionLayer, "_absorbed_paged"),
        "gathered": (LatentAttentionLayer, "_absorbed"),
        "expanded": (LatentAttentionLayer, "_expanded")},
    "experts": {
        "streamed": (GroupedExpertsHelper, "apply"),
        "sorted": (GroupedExpertsHelper, "apply_sorted"),
        "ragged": (RoutedMoELayer, "_held_ragged")},
    "state space": {
        "step": (ss, "single_step"),
        "scan": (ss.SelectiveScanHelper, "scan"),
        "stepwise": (ss, "stepwise_scan")},
    "delta rule": {
        "delta_step": (dr, "single_step"),
        "delta_chunk": (dr.DeltaRuleHelper, "chunked"),
        "delta_stepwise": (dr, "stepwise"),
        "delta_kernel": (dr.DeltaRuleHelper, "step_slots")},
    "kda": {
        "kda_step": (dr, "kda_single_step"),
        "kda_chunk": (dr.DeltaRuleHelper, "kda_chunked"),
        "kda_stepwise": (dr, "kda_stepwise"),
        "kda_kernel": (dr.DeltaRuleHelper, "kda_step_slots")},
}
FAMILY = {path: family for family, paths in SPIES.items() for path in paths}


def _mha_lm():
    from deeplearning4j_tpu.models.zoo import transformer_char_lm

    return transformer_char_lm(vocab_size=29, d_model=32, n_heads=4,
                               layers=2, max_cache=128, seed=5)


def _k2():
    from tests.test_latent_moe import toy_net

    return toy_net()[0]


def _jamba():
    from tests.test_jamba import toy_net

    return toy_net()[0]


def _olmo():
    from tests.test_olmo_hybrid import toy_net

    return toy_net()[0]


def _ling():
    from tests.test_ling import toy_net

    return toy_net()[0]


# by path: (net, its layers' kind, program, whether the program starts at
# position 0, the helper withheld or None, what stands in for the TPU:
# "paged" its Pallas attention kernels, "delta" the delta rule's kernel)
CASES = {
    "heads": (_mha_lm, "attention", "decode", False, None, "paged"),
    "rows": (_mha_lm, "attention", 16, True, None, "paged"),
    "lax": (_mha_lm, "attention", "decode", False, None, None),
    "gather": (_mha_lm, "attention", 16, True, "paged_attention", None),
    "paged": (_k2, "attention", "decode", False, None, None),
    "gathered": (_k2, "attention", 16, False, None, None),
    "expanded": (_k2, "attention", 16, True, None, None),
    "streamed": (_k2, "experts", "decode", False, None, None),
    "sorted": (_k2, "experts", 288, True, None, None),
    "ragged": (_k2, "experts", 16, True, "grouped_experts", None),
    "step": (_jamba, "recurrent", "decode", False, None, None),
    "scan": (_jamba, "recurrent", 16, True, None, None),
    "stepwise": (_jamba, "recurrent", 16, True, "selective_scan", None),
    "delta_step": (_olmo, "recurrent", "decode", False, None, None),
    "delta_chunk": (_olmo, "recurrent", 16, True, None, None),
    "delta_stepwise": (_olmo, "recurrent", 16, True, "delta_rule", None),
    "delta_kernel": (_olmo, "recurrent", "decode", False, None, "delta"),
    "kda_step": (_ling, "recurrent", "decode", False, None, None),
    "kda_chunk": (_ling, "recurrent", 16, True, None, None),
    "kda_stepwise": (_ling, "recurrent", 16, True, "delta_rule", None),
    "kda_kernel": (_ling, "recurrent", "decode", False, None, "delta"),
}


@pytest.fixture
def spied(monkeypatch):
    """The paths whose function executed on the device while the test
    ran.  The paged kernel's jit is emptied before and after, so its forms
    are traced through the spies and nothing traced through them outlives
    the test."""
    ran, jitted = set(), pa._pallas_paged
    for paths in SPIES.values():
        for path, (owner, name) in paths.items():
            real = getattr(owner, name)

            def spy(*a, _real=real, _path=path, **k):
                out = _real(*a, **k)
                jax.debug.callback(functools.partial(ran.add, _path))
                return out

            monkeypatch.setattr(owner, name, spy)
    jitted.clear_cache()
    yield ran
    jitted.clear_cache()


@pytest.mark.parametrize("path", list(CASES))
def test_the_traced_branch_is_the_path_the_table_names(path, spied,
                                                       monkeypatch):
    make, kind, program, from_zero, withheld, kernel = CASES[path]
    if withheld is not None:
        get = helpers.get_helper
        monkeypatch.setattr(helpers, "get_helper", lambda k: (
            None if k == withheld else get(k)))
    if kernel == "paged":
        monkeypatch.setattr(pa, "default_impl", lambda: "pallas")
    if kernel == "delta":
        monkeypatch.setattr(dr.DeltaRuleHelper, "kernel", True)
    bucket = 16 if program == "decode" else program
    progs = GenerationPrograms(make(), slots=4, pages_per_slot=24,
                               page_size=16, num_pages=97,
                               prefill_buckets=(bucket,))
    family = SPIES[FAMILY[path]]
    assert {p for k, p in progs.paths[(program, from_zero)]
            if k == kind and p in family} == {path}
    name = program if program == "decode" else f"prefill_{program}"
    jitted, tail = progs._compute_programs()[name]
    if name != "decode" and not from_zero:   # behind a shared page
        tail = (tail[0], np.full((1,), 16, np.int32), *tail[2:])
    jax.block_until_ready(jitted(progs.serving_params(), progs.net.net_state,
                                 progs.fresh_pools(), *tail))
    jax.effects_barrier()
    assert spied & set(family) == (
        {"heads", "rows"} if path == "heads" else {path})
