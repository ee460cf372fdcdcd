"""The sort aggregate's row order (``ops/segment.py`` ``_lexsort_perm``): the
chain of stable one-key sorts orders rows as one ``lax.sort`` over all the
keys does, and keeps tied rows in row order."""

import numpy as np
import pytest

import fugue_tpu.jax  # noqa: F401  the engine's own x64 setting


def _keys(kind: str, n: int, rng: np.random.Generator) -> list:
    if kind == "int64":  # both words matter: negatives, values past 2**32
        return [rng.choice(np.array([-(2**40), -5, -1, 0, 3, 2**32, 2**32 + 1, 2**62]), n)]
    if kind == "uint64_hash":  # the join's (invalid, hash) pair
        h = rng.integers(0, 2**64, n, dtype=np.uint64, endpoint=False)
        h[: n // 4] = h[n // 2 : n // 2 + n // 4]  # duplicate hashes
        return [rng.random(n) < 0.2, h]
    if kind == "q3":  # the sort aggregate's (invalid, orderkey, date, priority)
        return [
            rng.random(n) < 0.3,
            rng.integers(-3, 3, n).astype(np.int64) * (2**33) + rng.integers(0, 3, n),
            rng.integers(9100, 9104, n).astype(np.int32),
            rng.integers(0, 2, n).astype(np.int64),
        ]
    if kind == "float":  # NaN, signed zeros and infinities as lax.sort orders them
        vals = np.array([np.nan, -0.0, 0.0, -np.inf, np.inf, 1.5, -2.5])
        return [rng.integers(0, 3, n).astype(np.uint32), rng.choice(vals, n)]
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["int64", "uint64_hash", "q3", "float"])
def test_lexsort_perm_matches_one_multi_key_sort(kind):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from fugue_tpu.ops.segment import _lexsort_perm

    n = 4099
    keys = [jnp.asarray(k) for k in _keys(kind, n, np.random.default_rng(7))]
    perm = np.asarray(jax.jit(_lexsort_perm)(keys))
    assert perm.dtype == np.int32 and sorted(perm.tolist()) == list(range(n))
    iota = lax.iota(jnp.int32, n)
    want = lax.sort(tuple(keys) + (iota,), num_keys=len(keys), is_stable=True)
    for k, w in zip(keys, want[:-1]):
        np.testing.assert_array_equal(np.asarray(k)[perm], np.asarray(w))
    np.testing.assert_array_equal(perm, np.asarray(want[-1]))  # ties in row order
