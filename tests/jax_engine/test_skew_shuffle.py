"""Skew-safe multi-round shuffle: a hot key must not inflate the exchange
buffers (VERDICT r2 #6). The round capacity is forced tiny so the stress
runs many bounded rounds."""

import numpy as np
import pandas as pd
import pytest

import fugue_tpu.ops.shuffle as S
from fugue_tpu.collections import PartitionSpec
from fugue_tpu.jax import JaxExecutionEngine


@pytest.fixture(scope="module")
def engine():
    e = JaxExecutionEngine()
    yield e
    e.stop()


def test_multiround_exchange_hot_key(engine, monkeypatch):
    # one hot key owns ~70% of rows; cap rounds at 256 rows/dest/round
    monkeypatch.setattr(S, "SINGLE_ROUND_MAX_CAPACITY", 256)
    rng = np.random.default_rng(0)
    n = 20_000
    k = rng.integers(0, 50, n)
    k[: int(n * 0.7)] = 7  # hot key
    pdf = pd.DataFrame({"k": k, "v": rng.random(n)})
    jdf = engine.to_df(pdf)
    out = engine.repartition(jdf, PartitionSpec(algo="hash", by=["k"]))
    got = out.as_pandas().sort_values(["k", "v"]).reset_index(drop=True)
    exp = pdf.sort_values(["k", "v"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)
    # padded output stays near the true received max, not shards x hot size
    import jax

    arr = next(iter(out.device_cols.values()))
    per_shard = arr.shape[0] // 8
    hot = int((k == 7).sum())
    assert per_shard <= 2 * hot  # pow2 of max received, NOT 8x


def test_multiround_round_count(engine, monkeypatch):
    calls = {"n": 0}
    orig = S._get_compiled_round

    def counting(*a, **kw):
        fn = orig(*a, **kw)

        def wrapper(*args, **kwargs):
            calls["n"] += 1
            return fn(*args, **kwargs)

        return wrapper

    monkeypatch.setattr(S, "SINGLE_ROUND_MAX_CAPACITY", 128)
    monkeypatch.setattr(S, "_get_compiled_round", counting)
    pdf = pd.DataFrame({"k": [1] * 3000, "v": np.arange(3000.0)})
    jdf = engine.to_df(pdf)
    out = engine.repartition(jdf, PartitionSpec(algo="hash", by=["k"]))
    assert sorted(out.as_pandas()["v"]) == sorted(pdf["v"])
    # ~375 rows/shard to one dest at 128/round -> 3 bounded rounds
    assert calls["n"] >= 3


def test_multiround_with_masks_and_strings(engine, monkeypatch):
    monkeypatch.setattr(S, "SINGLE_ROUND_MAX_CAPACITY", 64)
    rng = np.random.default_rng(3)
    n = 2000
    pdf = pd.DataFrame(
        {
            "k": np.where(rng.random(n) < 0.8, 3, rng.integers(0, 10, n)),
            "s": rng.choice(["x", "y", "z"], n),
            "m": pd.array(
                np.where(rng.random(n) < 0.2, None, rng.integers(0, 99, n)),
                dtype="Int64",
            ),
        }
    )
    jdf = engine.to_df(pdf)
    out = engine.repartition(jdf, PartitionSpec(algo="hash", by=["k"]))
    got = out.as_pandas()
    g = got.sort_values(["k", "s", "m"], na_position="first").reset_index(drop=True)
    x = pdf.sort_values(["k", "s", "m"], na_position="first").reset_index(drop=True)
    pd.testing.assert_frame_equal(g, x, check_dtype=False)


def test_multiround_even_repartition(engine, monkeypatch):
    monkeypatch.setattr(S, "SINGLE_ROUND_MAX_CAPACITY", 64)
    pdf = pd.DataFrame({"v": np.arange(4000.0)})
    jdf = engine.to_df(pdf)
    # filter first so valid rows are unevenly spread, then rebalance
    from fugue_tpu.column import col, lit

    flt = engine.filter(jdf, col("v") < lit(1000.0))
    out = engine.repartition(flt, PartitionSpec(algo="even", num=8))
    assert sorted(out.as_pandas()["v"]) == sorted(range(1000))


@pytest.mark.parametrize(
    "max_count,local_rows,shards,plan",
    [
        # BASELINE config #3 on 4 chips: 25M rows a shard, 100,000 keys
        # hashed evenly — one all-to-all, not 48 rounds of 2**17
        (6_251_234, 25_000_000, 4, (1 << 23, 1)),
        # every row to one shard: at most ``shards`` rounds
        (25_000_000, 25_000_000, 4, (1 << 23, 3)),
        (1 << 25, 1 << 25, 8, (1 << 22, 8)),
        # small shards keep the floor
        (300, 4_000, 8, (512, 1)),
        (100_000, 100_000, 8, (1 << 17, 1)),
    ],
)
def test_exchange_plan_bounds_rounds(max_count, local_rows, shards, plan):
    assert S.exchange_plan(max_count, local_rows, shards) == plan
    _, rounds = plan
    assert rounds <= shards
