"""The seeded batch pool and weights."""
import jax
import numpy as np

from chipbench import data, weights
from chipbench.registry import family
from chipbench.tests.tiny import REPO, TINY


def _pool(seed, steps=3, n=4):
    return data.pool_fn(steps, n, 2, 16, 97)(weights.seed_key(seed))


def test_pool_repeats_exactly_for_a_seed():
    a, b = _pool(2**31 + 11), _pool(2**31 + 11)
    for x, y in zip(a, b):
        for k in ("tokens", "targets"):
            np.testing.assert_array_equal(x[k], y[k])


def test_pool_rows_differ_across_workers_steps_and_seeds():
    pool = _pool(5)
    toks = np.stack([np.asarray(b["tokens"]) for b in pool])  # (t, n, b, s)
    rows = toks.reshape(-1, toks.shape[-1])
    assert len({r.tobytes() for r in rows}) == len(rows)
    other = np.asarray(_pool(2**32 + 5)[0]["tokens"])
    assert not np.array_equal(other, toks[0])
    np.testing.assert_array_equal(pool[0]["targets"][..., :-1],
                                  pool[0]["tokens"][..., 1:])


def test_large_seeds_give_distinct_keys():
    keys = {tuple(np.asarray(weights.seed_key(s)).tolist())
            for s in (5, 2**32 + 5, 2**33 + 5, -5, 0)}
    assert len(keys) == 5


def test_weights_repeat_and_follow_the_param_dtype():
    m = dict(TINY["tiny-dense"]["model"], param_dtype="bfloat16")
    make = weights.params_fn(family(REPO, "qwen2"), m)
    a, b = make(weights.seed_key(3)), make(weights.seed_key(3))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == np.dtype("bfloat16")
        np.testing.assert_array_equal(x, y)
