"""The traffic generators: the same schedule for the same seed, another for
another seed, and the same amount of work for every seed."""

import numpy as np

from bench.tests import helpers as _h  # noqa: F401  (paths)
from bench.core import harness as H
from bench.core import weights as W
from bench.gen import open_loop as OL


def _sched(seed):
    tr = H.load("traffic", "chat_poisson")
    return OL.schedule(seed, 10.0, tr, 256000)


def test_open_loop_same_seed_same_schedule():
    a, b = _sched(5), _sched(5)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == [(r.due, r.max_new, r.prompt.tolist()) for r in b]


def test_open_loop_other_seed_other_tokens_same_sizes_and_times():
    a, b = _sched(5), _sched(3_000_000_123)
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == [(r.due, len(r.prompt), r.max_new) for r in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_open_loop_order_is_data_of_the_mix():
    tr = H.load("traffic", "chat_poisson")
    a = OL.schedule(5, 10.0, tr, 256000)
    b = OL.schedule(5, 10.0, dict(tr, order_seed=tr["order_seed"] + 1), 256000)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]


def test_open_loop_respects_the_traffic_file():
    tr = H.load("traffic", "chat_poisson")
    s = OL.schedule(11, 50.0, tr, 256000)
    assert len(s) == OL.count(tr, 50.0) == round(tr["rate_per_s"] * 50)
    lens = np.array([len(r.prompt) for r in s])
    outs = np.array([r.max_new for r in s])
    assert lens.min() >= tr["prompt"]["min"] and lens.max() <= tr["prompt"]["max"]
    assert outs.min() >= tr["output"]["min"] and outs.max() <= tr["output"]["max"]
    assert abs(np.median(lens) - tr["prompt"]["median"]) <= 0.05 * tr["prompt"]["median"]
    assert all(0.0 <= r.due < 50.0 for r in s)
    assert all(a.due <= b.due for a, b in zip(s, s[1:]))


def test_token_batches_same_seed_same_rows_and_rows_differ():
    a = W.token_batches(7, 4, 2, 32, 1000)
    b = W.token_batches(7, 4, 2, 32, 1000)
    c = W.token_batches(8, 4, 2, 32, 1000)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    rows = a.reshape(-1, 33)
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_weights_one_layer_equals_its_slice_of_the_stack():
    import jax.numpy as jnp

    dims = {"d_model": 16, "d_ff": 32, "num_heads": 2, "head_dim": 8}
    words = W.seed_words(3_000_000_123)
    full = W.leaf_value(words, "stack/scan/0/mlp/w_in", (3, 16, 32), jnp.bfloat16, dims)
    one = W.layer_value(words, "stack/scan/0/mlp/w_in", (16, 32), jnp.bfloat16, dims, 2)
    assert jnp.array_equal(full[2], one)
    other = W.leaf_value(W.seed_words(4), "stack/scan/0/mlp/w_in", (3, 16, 32), jnp.bfloat16, dims)
    assert not jnp.array_equal(full, other)
