"""``utils.sampling.sample_tokens`` does what the batch's policy arrays ask
for and no more: ``greedy`` (an argmax), ``draw`` (temperature + the
categorical draw) or ``filter`` (the top-k / top-p sorts), chosen inside the
one compiled program by ``sampling_path``.

- every path returns the ids of the function as it was before the switch
  (``_reference_sample_tokens`` below: kept here as the oracle);
- the compiled programs hold their sorts inside a conditional's branch;
- the numpy call of the path rule (the engine's counter) agrees with the
  ``jnp`` call (the program's branch);
- an engine run moves ``dl4j_layer_path_steps_total{kind="head"}`` by the
  path each step took, and a greedy request still equals
  ``models.decode.generate``.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.generation import GenerationEngine
from deeplearning4j_tpu.generation.programs import GenerationPrograms
from deeplearning4j_tpu.models.zoo import transformer_char_lm
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.utils.sampling import (
    SAMPLING_PATHS, _filter_logits, sample_tokens, sampling_path,
)

pytestmark = pytest.mark.generation

VOCAB = 29
B, V = 8, 512


def _reference_sample_tokens(logits, keys, token_idx, temperature, top_k,
                             top_p):
    """``sample_tokens`` as it stood before it chose a path: filter and
    draw for every row, then keep the argmax for the greedy ones."""
    step_keys = jax.vmap(jax.random.fold_in)(keys, token_idx)
    temp = jnp.asarray(temperature, logits.dtype)
    safe_t = jnp.where(temp > 0, temp, jnp.ones_like(temp))
    filtered = _filter_logits(logits / safe_t[:, None], top_k, top_p)
    drawn = jax.vmap(lambda k, l: jax.random.categorical(k, l, axis=-1))(
        step_keys, filtered)
    return jnp.where(temp > 0, drawn, jnp.argmax(logits, axis=-1))


def _policy(rows, temps=0.0, top_ks=0, top_ps=1.0):
    full = lambda v, dt: np.broadcast_to(np.asarray(v, dt), (rows,)).copy()
    return (full(temps, np.float32), full(top_ks, np.int32),
            full(top_ps, np.float32))


def _row(rows, i, value, rest):
    out = [rest] * rows
    out[i] = value
    return out


# name -> (policy arrays, the path they must take)
CASES = {
    "all_greedy": (_policy(B), "greedy"),
    "temperature_only": (_policy(B, [0.0, 0.7, 1.0, 0.0, 1.3, 0.0, 0.9,
                                     0.0]), "draw"),
    "all_draw_no_filter": (_policy(B, 0.8), "draw"),
    "one_top_k_row": (_policy(B, _row(B, 2, 0.9, 0.0),
                              _row(B, 2, 5, 0)), "filter"),
    "one_top_p_row": (_policy(B, _row(B, 5, 1.1, 0.0), 0,
                              _row(B, 5, 0.6, 1.0)), "filter"),
    "top_k_and_top_p_on_one_row": (
        _policy(B, _row(B, 0, 0.7, 0.0), _row(B, 0, 6, 0),
                _row(B, 0, 0.85, 1.0)), "filter"),
    "filter_row_among_drawing_rows": (
        _policy(B, 0.9, _row(B, 7, 3, 0)), "filter"),
    "greedy_rows_carry_filters": (_policy(B, 0.0, 5, 0.5), "greedy"),
    "greedy_filters_beside_plain_draws": (
        _policy(B, [0.0, 0.8] * 4, [4, 0] * 4, [0.3, 1.0] * 4), "draw"),
    "one_row_greedy": (_policy(1), "greedy"),
    "one_row_draw": (_policy(1, 0.8), "draw"),
    "one_row_top_k": (_policy(1, 0.8, 4), "filter"),
    "one_row_top_p": (_policy(1, 1.2, 0, 0.7), "filter"),
    "one_row_greedy_with_filter": (_policy(1, 0.0, 3, 0.5), "greedy"),
}


def _inputs(rows, seed=0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(rows, V).astype(np.float32) * 3)
    keys = jnp.asarray(rng.randint(0, 2 ** 31, (rows, 2)).astype(np.uint32))
    token_idx = jnp.asarray(rng.randint(0, 100, rows).astype(np.int32))
    return logits, keys, token_idx


@pytest.mark.parametrize("name", sorted(CASES))
def test_ids_equal_the_unswitched_function(name):
    policy, _ = CASES[name]
    new, old = jax.jit(sample_tokens), jax.jit(_reference_sample_tokens)
    for seed in range(3):
        args = _inputs(len(policy[0]), seed)
        np.testing.assert_array_equal(np.asarray(new(*args, *policy)),
                                      np.asarray(old(*args, *policy)))


@pytest.mark.parametrize("name", sorted(CASES))
def test_path_rule_numpy_and_jnp_agree(name):
    policy, path = CASES[name]
    host = sampling_path(*policy)
    device = jax.jit(sampling_path)(*map(jnp.asarray, policy))
    assert SAMPLING_PATHS[host] == SAMPLING_PATHS[int(device)] == path


@pytest.mark.parametrize("vocab", [20480, 49152])
def test_disabled_filter_drops_at_most_rounding(vocab):
    """What ``draw`` leaves out, at the served vocabulary widths: with
    ``top_k < 1`` and ``top_p >= 1`` the filter keeps every token but a
    tail that its f32 cumulative sum rounds away, under 1e-6 of a row's
    mass, and never the argmax."""
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(4, vocab).astype(np.float32) * 5)
    kept = _filter_logits(logits, jnp.zeros(4, jnp.int32),
                          jnp.ones(4, jnp.float32))
    cut = np.asarray(kept != logits)
    mass = (np.asarray(jax.nn.softmax(logits, axis=-1)) * cut).sum(axis=1)
    assert mass.max() < 1e-6
    np.testing.assert_array_equal(np.asarray(jnp.argmax(kept, axis=-1)),
                                  np.asarray(jnp.argmax(logits, axis=-1)))


def _sorts_by_computation(hlo_text):
    """``[(computation name, is entry)]`` of every ``sort`` instruction in
    a compiled module's text."""
    found, comp = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head:
            comp = (head.group(2), bool(head.group(1)))
        elif re.search(r"\ssort\(", line):
            found.append(comp)
    return found


def _branch_computations(hlo_text):
    names = set()
    for groups in re.findall(r"branch_computations=\{([^}]*)\}", hlo_text):
        names.update(n.strip().lstrip("%") for n in groups.split(","))
    for pair in re.findall(
            r"true_computation=%?([\w.\-]+), false_computation=%?([\w.\-]+)",
            hlo_text):
        names.update(pair)
    return names


def _assert_sorts_only_in_branches(hlo_text):
    sorts = _sorts_by_computation(hlo_text)
    branches = _branch_computations(hlo_text)
    assert sorts, "the filter branch's sorts are gone from the program"
    assert branches
    for comp, is_entry in sorts:
        assert not is_entry, "a sort at the top level of the program"
        assert comp in branches, f"sort in {comp}, no conditional's branch"


def test_sample_tokens_sorts_only_inside_the_conditional():
    args = _inputs(B)
    text = jax.jit(sample_tokens).lower(
        *args, *CASES["one_top_k_row"][0]).compile().as_text()
    _assert_sorts_only_in_branches(text)


@pytest.fixture(scope="module")
def lm():
    return transformer_char_lm(vocab_size=VOCAB, d_model=32, n_heads=4,
                               layers=2, max_cache=128, seed=12345)


@pytest.mark.parametrize("program", ["decode", "prefill_8"])
def test_generation_programs_sort_only_inside_the_conditional(lm, program):
    progs = GenerationPrograms(lm, slots=4, pages_per_slot=4, page_size=4,
                               num_pages=17, prefill_buckets=(8,))
    text = progs.lowered()[program].compile().as_text()
    _assert_sorts_only_in_branches(text)


def _counts(registry):
    return {(stage, path): registry.get_value(
        "dl4j_layer_path_steps_total", stage=stage, kind="head",
        path=path) or 0
        for stage in ("prefill", "decode") for path in SAMPLING_PATHS}


def test_engine_counts_each_step_under_its_path(lm, rng):
    """One request at a time, so each step's path is that request's own:
    a request of n tokens is one prefill and n - 1 decode steps."""
    from deeplearning4j_tpu.models.decode import generate

    registry = MetricsRegistry()
    eng = GenerationEngine(lm, slots=4, page_size=4, max_context=32,
                           prefill_buckets=(8,), registry=registry)
    eng.start()
    try:
        prompt = rng.randint(0, VOCAB, (1, 6))
        greedy = eng.generate(prompt[0], 9).tolist()
        # a greedy request's top_k / top_p do not count
        greedy_filtered = eng.generate(prompt[0], 4, top_k=3,
                                       top_p=0.5).tolist()
        eng.generate(prompt[0], 6, temperature=0.9, seed=3)
        eng.generate(prompt[0], 5, temperature=0.9, top_k=5, seed=4)
        eng.generate(prompt[0], 3, temperature=1.1, top_p=0.8, seed=5)
    finally:
        eng.stop()      # joins the loop: the last step's counter is in
    assert greedy == generate(lm, prompt, 9, temperature=0.0)[0].tolist()
    assert greedy_filtered == greedy[:4]
    assert _counts(registry) == {
        ("prefill", "greedy"): 2, ("decode", "greedy"): 8 + 3,
        ("prefill", "draw"): 1, ("decode", "draw"): 5,
        ("prefill", "filter"): 2, ("decode", "filter"): 4 + 2}
    assert registry.get_value("dl4j_decode_steps_total") == 22


def test_engine_mixed_batch_takes_the_filter_path_and_greedy_holds(lm, rng):
    """A nucleus request beside a greedy one: the steps they share run the
    filter path for the whole batch, and the greedy stream is still the
    compiled scan's, token for token."""
    from deeplearning4j_tpu.models.decode import generate

    registry = MetricsRegistry()
    eng = GenerationEngine(lm, slots=4, page_size=4, max_context=32,
                           prefill_buckets=(8,), registry=registry)
    eng.start()
    try:
        prompt = rng.randint(0, VOCAB, (1, 5))
        alone = eng.generate(prompt[0], 7, temperature=0.8, top_p=0.9,
                             seed=11).tolist()
        sampled = eng.submit(prompt[0].tolist(), 7, temperature=0.8,
                             top_p=0.9, seed=11)
        greedy = eng.submit(prompt[0].tolist(), 12)
        got_sampled = sampled.result(timeout=60)
        got_greedy = greedy.result(timeout=60)
    finally:
        eng.stop()
    assert got_greedy == generate(lm, prompt, 12,
                                  temperature=0.0)[0].tolist()
    assert got_sampled == alone
    counts = _counts(registry)
    assert counts[("prefill", "filter")] == 2
    assert counts[("prefill", "greedy")] == 1
    assert counts[("prefill", "draw")] == counts[("decode", "draw")] == 0
    # each sampled request decodes 6 steps, all on the filter path; the
    # greedy one's 11 are filter steps while it shares the batch
    assert counts[("decode", "filter")] == 12
    assert 5 <= counts[("decode", "greedy")] <= 11
    assert (counts[("decode", "filter")] + counts[("decode", "greedy")]
            == registry.get_value("dl4j_decode_steps_total"))
