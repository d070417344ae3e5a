"""Every run of a cell offers the same work: the same number of requests,
the same multiset of (prompt length, output length) in every block, the
same prompts in the same order, all due when the window opens, whatever its
``--seed``, which draws only the sample that the correctness check reads.
Weights come from the configuration alone."""
import collections
import types

import _paths  # noqa: F401
import numpy as np
import pytest

from harness import correct, registry, traffic

MIXES = registry.names("mixes", ".json")
SEEDS = list(range(6)) + [2**31 + 7]


def _lengths(offered):
    return collections.Counter((len(o.prompt), o.max_new_tokens)
                               for o in offered)


@pytest.mark.parametrize("name", MIXES)
def test_every_run_offers_the_same_work(name):
    mix = registry.load_json("mixes", name)
    a = traffic.schedule(mix, 49152)
    b = traffic.schedule(mix, 49152)
    assert len(a) == mix["requests"]
    assert [(o.prompt.tobytes(), o.max_new_tokens, o.due_s) for o in a] == \
        [(o.prompt.tobytes(), o.max_new_tokens, o.due_s) for o in b]
    assert all(o.due_s == 0.0 for o in a)
    # the blocks hold the same lengths in different orders
    k = mix["block"]
    orders = {tuple((len(o.prompt), o.max_new_tokens) for o in a[i:i + k])
              for i in range(0, len(a) - k + 1, k)}
    assert len(orders) > 1 or len(a) < 2 * k


@pytest.mark.parametrize("name", MIXES)
def test_whole_blocks_have_the_same_composition(name):
    mix = registry.load_json("mixes", name)
    k = mix["block"]
    pairs = collections.Counter(traffic.length_pairs(mix))
    offered = traffic.schedule(mix, 49152)
    for b in range(len(offered) // k):
        assert _lengths(offered[b * k:(b + 1) * k]) == pairs


@pytest.mark.parametrize("name", MIXES)
def test_lengths_are_the_quantiles_of_the_stated_means(name):
    mix = registry.load_json("mixes", name)
    k = mix["block"]
    for key, col in (("prompt", 0), ("output", 1)):
        got = sorted(pair[col] for pair in traffic.length_pairs(mix))
        mean = mix[key]["mean"]
        assert got == traffic.quantile_lengths(mix[key], k)
        # the midpoint quantiles of an exponential fall a little short of
        # its mean (the tail past the last one is left out)
        assert 0.9 * mean < sum(got) / k < mean


def test_exponential_quantiles_by_hand():
    # mean 100, 4 midpoint quantiles: -100 ln(1 - (i + 1/2) / 4)
    assert traffic.quantile_lengths({"mean": 100.0}, 4) == [13, 47, 98, 208]
    assert traffic.quantile_lengths({"mean": 0.5}, 2)[0] == 1


def test_the_seed_draws_the_sample_with_the_longest_in_it():
    def rec(i, n_prompt, n_gen):
        req = types.SimpleNamespace(done=True, generated=[1] * n_gen)
        return types.SimpleNamespace(
            offered=types.SimpleNamespace(index=i, prompt=[0] * n_prompt),
            request=req)

    records = [rec(i, 50 + i, 200) for i in range(40)]
    picks = [[r.offered.index for r in correct.sample(records, s)]
             for s in SEEDS]
    assert all(p[0] == 39 for p in picks)
    assert all(sum(200 for _ in p) >= correct.SAMPLE_TOKENS for p in picks)
    assert picks == [[r.offered.index for r in correct.sample(records, s)]
                     for s in SEEDS]
    assert len({tuple(sorted(p)) for p in picks}) > 1


def test_prompts_hold_vocabulary_ids_of_the_stated_lengths():
    rng = np.random.default_rng(0)
    p = traffic.make_prompt(rng, 400, 1000)
    assert p.dtype == np.int32 and len(p) == 400
    assert p.min() >= 2 and p.max() < 1000
    assert len(set(p.tolist())) > 200


def test_weights_depend_on_the_configuration_seed_only():
    import jax

    from harness import weights

    c = {"hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
         "vocab_size": 300, "mlp_gated": True, "torch_dtype": "bfloat16",
         "weight_seed": 3}
    a, b = weights.make(c), weights.make(c)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    d = weights.make(dict(c, weight_seed=4))
    assert not np.array_equal(a["embed"], d["embed"])
    assert a["embed"].shape == (512, 32)
    assert a["segments"][0][0]["mlp"]["w_gate"].shape == (2, 32, 64)
