"""Device-resident dense aggregates: the result frame must stay on device
(no host materialization) and remain a first-class input to later device
ops. Mirrors the reference's aggregate contract
(/root/reference/fugue/execution/execution_engine.py:898-939) with the
finish running on the mesh instead of a backend SQL engine."""

from datetime import date

import numpy as np
import pandas as pd
import pytest

from fugue_tpu.collections import PartitionSpec
from fugue_tpu.column import SelectColumns, col, functions as ff, lit
from fugue_tpu.execution.native_execution_engine import NativeExecutionEngine
from fugue_tpu.jax import JaxExecutionEngine
from fugue_tpu.obs import get_tracer

SPEC = PartitionSpec(by=["k"])


@pytest.fixture(scope="module")
def eng():
    return JaxExecutionEngine()


def test_dense_aggregate_stays_on_device(eng):
    rng = np.random.default_rng(7)
    pdf = pd.DataFrame(
        {"k": rng.integers(0, 500, 50_000), "v": rng.random(50_000)}
    )
    res = eng.aggregate(
        eng.to_df(pdf),
        SPEC,
        [
            ff.sum(col("v")).alias("s"),
            ff.count(col("v")).alias("n"),
            ff.avg(col("v")).alias("m"),
            ff.min(col("v")).alias("lo"),
            ff.max(col("v")).alias("hi"),
        ],
    )
    # the proof of device residency: no host table, explicit valid mask
    assert res.host_table is None
    assert res.valid_mask is not None
    got = res.as_pandas().sort_values("k").reset_index(drop=True)
    exp = (
        pdf.groupby("k")
        .agg(
            s=("v", "sum"),
            n=("v", "count"),
            m=("v", "mean"),
            lo=("v", "min"),
            hi=("v", "max"),
        )
        .reset_index()
    )
    assert np.allclose(got[["s", "m", "lo", "hi"]], exp[["s", "m", "lo", "hi"]])
    assert (got["n"].to_numpy() == exp["n"].to_numpy()).all()


def test_all_null_group_and_sparse_range(eng):
    pdf = pd.DataFrame(
        {
            "k": np.array([5, 5, 900, 900, 42], dtype=np.int32),
            "v": [1.0, 2.0, np.nan, np.nan, 7.0],
        }
    )
    res = eng.aggregate(
        eng.to_df(pdf),
        SPEC,
        [ff.sum(col("v")).alias("s"), ff.avg(col("v")).alias("m")],
    )
    assert res.host_table is None
    assert res.count() == 3  # lazy count over the valid mask
    got = res.as_pandas().sort_values("k").reset_index(drop=True)
    # int32 key dtype survives the device finish
    assert str(res.schema["k"].type) == "int32"
    assert got["k"].tolist() == [5, 42, 900]
    assert got["s"].tolist()[:2] == [3.0, 7.0] and np.isnan(got["s"][2])
    assert np.isnan(got["m"][2])


def test_aggregate_of_filtered_frame_then_downstream_filter(eng):
    pdf = pd.DataFrame(
        {"k": np.arange(100) % 7, "v": np.arange(100, dtype=float)}
    )
    f = eng.filter(eng.to_df(pdf), col("v") < 50)
    r = eng.aggregate(
        f, SPEC, [ff.count(col("v")).alias("n"), ff.sum(col("v")).alias("s")]
    )
    assert r.host_table is None
    # the aggregate result is itself a valid device input to later ops
    r2 = eng.filter(r, col("s") > 100.0)
    exp = (
        pdf.query("v<50")
        .groupby("k")
        .agg(n=("v", "count"), s=("v", "sum"))
        .reset_index()
        .query("s>100")
        .reset_index(drop=True)
    )
    got = r2.as_pandas().sort_values("k").reset_index(drop=True)
    assert (got["k"].to_numpy() == exp["k"].to_numpy()).all()
    assert np.allclose(got["s"], exp["s"])


def test_int_sum_min_max_dtypes(eng):
    pdf = pd.DataFrame({"k": np.arange(20) % 3, "x": np.arange(20)})
    r = eng.aggregate(
        eng.to_df(pdf),
        SPEC,
        [
            ff.sum(col("x")).alias("s"),
            ff.min(col("x")).alias("lo"),
            ff.max(col("x")).alias("hi"),
        ],
    )
    assert r.host_table is None
    got = r.as_pandas().sort_values("k").reset_index(drop=True)
    exp = (
        pdf.groupby("k")
        .agg(s=("x", "sum"), lo=("x", "min"), hi=("x", "max"))
        .reset_index()
    )
    assert (got.to_numpy() == exp.to_numpy()).all()
    assert str(r.schema["s"].type) == "int64"


def test_host_key_range_declines_masked_and_encoded_cols(eng):
    # host-side min/max skips NULLs, but the device column holds fill
    # values — the two probes would disagree, so the host path must
    # decline for masked/encoded columns (device probe stays authoritative)
    pdf = pd.DataFrame(
        {
            "k": pd.array([5, 10, None], dtype="Int64"),
            "s": ["a", "b", "c"],
            "p": [1, 2, 3],
        }
    )
    jdf = eng.to_df(pdf)
    assert jdf._host_key_range("k") is None
    assert jdf._host_key_range("s") is None
    assert jdf._host_key_range("p") == (1, 3)


def test_masked_int_values_keep_host_finish_exact(eng):
    # nullable int64 goes through the hi/lo host merge (device finish must
    # decline) and stays exact at 2^62 scale
    big = 1 << 62
    pdf = pd.DataFrame(
        {
            "k": [0, 0, 1, 1],
            "x": pd.array([big, 3, None, None], dtype="Int64"),
        }
    )
    r = eng.aggregate(eng.to_df(pdf), SPEC, [ff.sum(col("x")).alias("s")])
    got = r.as_pandas().sort_values("k").reset_index(drop=True)
    assert got["s"][0] == big + 3
    assert pd.isna(got["s"][1])


# ---------------------------------------------------------------------------
# grouped selects whose keys are renamed or computed, or whose arguments are
# expressions: one device projection makes helper columns, then the device
# aggregate runs; each is compared with the native engine
# ---------------------------------------------------------------------------


@pytest.fixture
def traced():
    tr = get_tracer()
    tr.clear()
    tr.enable()
    yield tr
    tr.disable()
    tr.clear()


def _items(n=6000, seed=11):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "okey": rng.integers(0, 700, n),
            "flag": rng.choice(["A", "N", "R"], n),
            "status": rng.choice(["F", "O"], n),
            "qty": rng.integers(1, 51, n).astype(float),
            "price": rng.random(n) * 1000,
            "disc": rng.integers(0, 11, n) / 100,
            "tax": rng.integers(0, 9, n) / 100,
            "ship": [date(1992, 1, 1) + pd.Timedelta(days=int(d)) for d in rng.integers(0, 2500, n)],
        }
    )


def _orders(seed=12):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "okey": np.arange(700),
            "odate": [date(1995, 1, 1) + pd.Timedelta(days=int(d)) for d in rng.integers(0, 40, 700)],
            "prio": rng.integers(0, 3, 700),
        }
    )


def _spans(tr):
    recs = tr.records()
    host = [(r["args"]["op"], r["args"]["reason"]) for r in recs if r["name"] == "host.op"]
    proj = [r for r in recs if r["name"] == "agg.project"]
    return host, proj


def _same(got, exp, keys, exact):
    got = got.sort_values(keys).reset_index(drop=True)
    exp = exp.sort_values(keys).reset_index(drop=True)
    assert list(got.columns) == list(exp.columns)
    assert len(got) == len(exp)
    for c in got.columns:
        if c in keys or c in exact:
            assert got[c].tolist() == exp[c].tolist(), c
        else:
            np.testing.assert_allclose(
                got[c].to_numpy(float), exp[c].to_numpy(float), rtol=1e-12, equal_nan=True
            )


def _both(eng, fn, *pdfs):
    """The result of ``fn(engine, *frames)`` on the JAX engine (tracing on)
    and on the native engine."""
    tr = get_tracer()
    got = fn(eng, *[eng.to_df(p) for p in pdfs]).as_pandas()
    tr.disable()
    nat = NativeExecutionEngine()
    exp = fn(nat, *[nat.to_df(p) for p in pdfs]).as_pandas()
    return got, exp


def test_q1_shaped_select_aggregates_on_device(eng, traced):
    disc_price = col("price") * (1 - col("disc"))
    cols = SelectColumns(
        col("flag"),
        col("status"),
        ff.sum(col("qty")).alias("sum_qty"),
        ff.sum(disc_price).alias("sum_disc_price"),
        ff.sum(disc_price * (1 + col("tax"))).alias("sum_charge"),
        ff.avg(col("qty")).alias("avg_qty"),
        ff.avg(col("price") * (1 - col("disc"))).alias("avg_disc_price"),
        ff.count(lit(1)).alias("count_order"),
    )
    where = col("ship") <= lit(date(1998, 9, 2))

    def q(e, df):
        return e.select(df, cols, where=where)

    got, exp = _both(eng, q, _items())
    _same(got, exp, ["flag", "status"], ["count_order"])
    host, proj = _spans(traced)
    assert not any(op in ("select", "aggregate") for op, _ in host)
    (p,) = proj  # SUM and AVG of the same expression share one helper
    assert p["args"]["args"] == ["__agg_arg0", "__agg_arg1"]
    assert p["args"]["keys"] == []


def test_q3_shaped_select_after_join_aggregates_on_device(eng, traced):
    cols = SelectColumns(
        col("okey").alias("l_orderkey"),
        ff.sum(col("price") * (1 - col("disc"))).alias("revenue"),
        col("odate"),
        col("prio"),
    )

    def q(e, items, orders):
        return e.select(e.join(orders, items, how="inner", on=["okey"]), cols)

    got, exp = _both(eng, q, _items(), _orders())
    assert list(got.columns) == ["l_orderkey", "revenue", "odate", "prio"]
    _same(got, exp, ["l_orderkey", "odate", "prio"], [])
    host, proj = _spans(traced)
    assert not any(op in ("select", "aggregate") for op, _ in host)
    (p,) = proj
    assert p["args"]["args"] == ["__agg_arg0"] and p["args"]["keys"] == []


def test_expression_over_nan_floats_keeps_null_semantics(eng, traced):
    pdf = pd.DataFrame(
        {
            "k": np.arange(40) % 4,
            "a": [np.nan if i % 3 == 0 else float(i) for i in range(40)],
            "b": [np.nan if i % 5 == 0 else 0.5 * i for i in range(40)],
        }
    )
    # group 0 of k2 sees only NULL products: SUM and AVG are NULL there
    pdf.loc[pdf["k"] == 0, "a"] = np.nan
    cols = SelectColumns(
        col("k"),
        (col("k") + 1).alias("k2"),
        ff.sum(col("a") * col("b")).alias("s"),
        ff.avg(col("a") - col("b")).alias("m"),
        ff.count(col("a") + col("b")).alias("n"),
        ff.min(col("a") * 2).alias("lo"),
        ff.max(col("b") / 2).alias("hi"),
    )

    def q(e, df):
        return e.select(df, cols)

    got, exp = _both(eng, q, pdf)
    _same(got, exp, ["k", "k2"], ["n"])
    assert got.sort_values("k")["s"].isna().tolist() == [True, False, False, False]
    host, proj = _spans(traced)
    assert not any(op in ("select", "aggregate") for op, _ in host)
    (p,) = proj
    assert p["args"]["keys"] == ["__agg_key0"]
    assert len(p["args"]["args"]) == 5


def test_argument_over_a_dictionary_column_falls_back(eng, traced):
    cols = SelectColumns(
        col("status"),
        ff.sum(col("qty")).alias("s"),
        ff.max(col("flag") == "A").alias("any_a"),
    )

    def q(e, df):
        return e.select(df, cols)

    got, exp = _both(eng, q, _items(500))
    _same(got, exp, ["status"], ["any_a"])
    host, proj = _spans(traced)
    assert host == [("select", "agg_plan_refused:agg_of_expression")]
    assert proj == []


def test_plain_select_plans_as_before(eng, traced):
    # bare keys and arguments, and a key that is only renamed, need no
    # projection: the device aggregate reads the frame's own columns
    for keys in ([col("flag"), col("status")], [col("flag").alias("f"), col("status")]):
        cols = SelectColumns(
            *keys,
            ff.sum(col("price")).alias("s"),
            ff.avg(col("qty")).alias("m"),
            ff.count(lit(1)).alias("n"),
        )

        def q(e, df):
            return e.select(df, cols)

        traced.enable()
        got, exp = _both(eng, q, _items(800))
        _same(got, exp, [keys[0].output_name, "status"], ["n"])
        host, proj = _spans(traced)
        assert host == [] and proj == []
        traced.clear()


def test_one_source_under_two_names(eng, traced):
    cols = SelectColumns(
        col("status").alias("x"),
        col("status").alias("y"),
        ff.sum(col("qty") * 2).alias("status"),
    )

    def q(e, df):
        return e.select(df, cols)

    got, exp = _both(eng, q, _items(300))
    assert list(got.columns) == ["x", "y", "status"]
    _same(got, exp, ["x", "y"], [])
    host, proj = _spans(traced)
    assert host == []
    (p,) = proj
    assert p["args"]["keys"] == ["__agg_key0", "__agg_key1"]


def _refuse(*args, **kwargs):
    raise AssertionError("this kernel should not run")


def test_few_dictionary_groups_take_the_masked_kernel(eng, monkeypatch):
    # two dictionary-coded keys with NULLs pack into 4 x 3 groups, so the
    # dense kernel fills its 16 buckets by masked reductions (one step, on
    # every platform), and the sort kernel does not run
    from fugue_tpu.ops import segment

    monkeypatch.setattr(segment, "_get_compiled_kernel", _refuse)
    assert segment.dense_buckets(4 * 3) <= segment._MASKED_STEP
    rng = np.random.default_rng(5)
    n = 3000
    pdf = pd.DataFrame(
        {
            "flag": rng.choice(["A", "N", "R", None], n),
            "status": rng.choice(["F", "O", None], n),
            "tag": rng.choice(["x", "y", "z"], n),
            "v": np.where(rng.random(n) < 0.2, np.nan, rng.random(n) * 100),
            "q": rng.integers(0, 50, n),
        }
    )
    cols = SelectColumns(
        col("flag"),
        col("status"),
        ff.sum(col("v")).alias("s"),
        ff.avg(col("v") * (1 - col("q") / 100)).alias("m"),
        ff.count(col("v")).alias("n"),
        ff.min(col("v")).alias("lo"),
        ff.max(col("q")).alias("hi"),
        ff.min(col("tag")).alias("first_tag"),
        ff.count(lit(1)).alias("rows"),
    )

    def q(e, df):
        return e.select(df, cols, where=col("q") > 3)

    got, exp = _both(eng, q, pdf)
    got = got.fillna({"flag": "<null>", "status": "<null>"})
    exp = exp.fillna({"flag": "<null>", "status": "<null>"})
    _same(got, exp, ["flag", "status"], ["n", "hi", "first_tag", "rows"])
    assert len(got) == 12


def test_many_dictionary_groups_scatter_into_the_dense_table(eng, monkeypatch):
    # more groups than one masked step holds, which on a CPU mesh is where
    # the scatter starts: the packed index still fits a dense table, which
    # the rows scatter into; no sort
    from fugue_tpu.ops import segment

    monkeypatch.setattr(segment, "_get_compiled_kernel", _refuse)
    rng = np.random.default_rng(6)
    names = [f"k{i:03d}" for i in range(2 * segment._MASKED_STEP)]
    assert segment.dense_buckets(len(names) + 1) > segment._MASKED_STEP
    keys = names + list(rng.choice(names, 2000))
    pdf = pd.DataFrame({"k": keys, "v": rng.random(len(keys))})
    cols = SelectColumns(col("k"), ff.sum(col("v") * 2).alias("s"))

    def q(e, df):
        return e.select(df, cols)

    got, exp = _both(eng, q, pdf)
    _same(got, exp, ["k"], [])


def test_having_reads_the_renamed_key_and_an_expression_aggregate(eng, traced):
    rng = np.random.default_rng(8)
    n = 1500
    pdf = pd.DataFrame({"k": rng.integers(0, 30, n), "a": rng.random(n), "b": rng.integers(0, 9, n)})
    cols = SelectColumns(col("k").alias("kk"), ff.sum(col("a") * col("b")).alias("t"))
    having = (col("kk") > 4) & (ff.sum(col("a") * col("b")) > 100.0)

    def q(e, df):
        return e.select(df, cols, having=having)

    got, exp = _both(eng, q, pdf)
    assert 0 < len(got) < 25
    _same(got, exp, ["kk"], [])
    host, proj = _spans(traced)
    assert host == [("filter", "having_after_agg")]
    assert len(proj) == 1


@pytest.mark.parametrize("key", [col("k"), col("k").alias("kk")])
def test_host_resident_key_with_an_expression_argument_falls_back(eng, traced, key):
    # binary stays on the host: the select runs there, as before the
    # pre-projection, and no helper column is made
    rng = np.random.default_rng(9)
    n = 400
    pdf = pd.DataFrame(
        {
            "k": rng.choice([b"x", b"y", b"z"], n),
            "a": rng.random(n),
            "b": rng.integers(0, 7, n),
        }
    )
    cols = SelectColumns(key, ff.sum(col("a") * col("b")).alias("s"))

    def q(e, df):
        return e.select(df, cols)

    got, exp = _both(eng, q, pdf)
    _same(got, exp, [key.output_name], [])
    host, proj = _spans(traced)
    assert host == [("select", "agg_plan_refused:key_not_on_device")]
    assert proj == []


def test_dictionary_and_int_keys_take_the_sort_kernel(eng, monkeypatch):
    # an int key whose range no dense table holds, beside a dictionary key:
    # the sort kernel
    from fugue_tpu.ops import segment

    monkeypatch.setattr(segment, "_get_compiled_dense", _refuse)
    rng = np.random.default_rng(10)
    n = 1200
    pdf = pd.DataFrame(
        {
            "flag": rng.choice(["A", "N", "R"], n),
            "okey": rng.integers(0, 90, n) * 10**9,
            "a": rng.random(n),
        }
    )
    cols = SelectColumns(col("flag"), col("okey"), ff.sum(col("a") * 3).alias("s"))

    def q(e, df):
        return e.select(df, cols)

    got, exp = _both(eng, q, pdf)
    _same(got, exp, ["flag", "okey"], [])


def test_int_and_date_keys_of_small_range_pack_into_the_dense_table(eng, monkeypatch):
    # two int keys (one of them large, one with NULLs) and a date key, each
    # of small range: probed, packed into one index, no sort
    from fugue_tpu.ops import segment

    monkeypatch.setattr(segment, "_get_compiled_kernel", _refuse)
    rng = np.random.default_rng(13)
    n = 3000
    pdf = pd.DataFrame(
        {
            "year": rng.integers(1992, 1999, n) + 10**12,
            "month": pd.array(
                np.where(rng.random(n) < 0.1, None, rng.integers(1, 13, n)), dtype="Int64"
            ),
            "day": [date(1995, 1, 1) + pd.Timedelta(days=int(d)) for d in rng.integers(0, 5, n)],
            "price": rng.random(n) * 1000,
            "disc": rng.integers(0, 11, n) / 100,
        }
    )
    cols = SelectColumns(
        col("year"),
        col("month"),
        col("day"),
        ff.sum(col("price") * (1 - col("disc"))).alias("s"),
        ff.count(lit(1)).alias("n"),
    )

    def q(e, df):
        return e.select(df, cols)

    got, exp = _both(eng, q, pdf)
    got = got.fillna({"month": -1})
    exp = exp.fillna({"month": -1})
    _same(got, exp, ["year", "month", "day"], ["n"])
