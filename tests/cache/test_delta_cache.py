"""Partition-level incremental recompute (``fugue_tpu/cache/delta.py``,
docs/cache.md "Incremental recompute") — ISSUE 9.

The checklist:

- **delta parity matrix**: over a GROWN parquet directory, the warm run
  serves cached partitions + recomputes only the new one, bit-identical
  to a cache-off full recompute, across fused-chain / filter /
  dense-aggregate (sum/count/avg/min/max) shapes, on the jax AND native
  engines, optimizer ON and OFF — including NULL values and group keys
  that first appear in the delta;
- **grown single files**: an appended-to csv with an unchanged prefix
  (stored digest) recomputes only the appended rows;
- **the refusal ladder**: changed partition contents, reordered/deleted
  partitions, non-row-local verbs, disabled conf — every refusal
  degrades to PR 5 whole-task semantics with the reason visible in
  ``workflow.explain()``, and results stay correct;
- **store consistency**: ``disk_max_entries`` mtime-LRU eviction keeps
  manifest + artifacts consistent (an evicted partition artifact
  invalidates ITS manifest, not the whole cache);
- **runtime fallback**: a delta recompute that fails mid-run falls back
  in place to a full recompute from the source;
- **persist / restart**: a delta-merged ``persist()`` publishes the
  MERGED artifact, so a later exact-match run on a FRESH engine takes
  the whole-task disk hit;
- **observability**: delta counters flatten onto a valid Prometheus
  exposition; ``explain()`` renders ``DELTA[k/n partitions]``.

The two-process append race lives with its PR 5 siblings in
``test_result_cache.py``.
"""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from fugue_tpu import FugueWorkflow
from fugue_tpu.column import col, functions as ff
from fugue_tpu.constants import (
    FUGUE_TPU_CONF_CACHE_DELTA_ENABLED,
    FUGUE_TPU_CONF_CACHE_DIR,
    FUGUE_TPU_CONF_CACHE_ENABLED,
    FUGUE_TPU_CONF_PLAN_OPTIMIZE,
)
from fugue_tpu.execution import NativeExecutionEngine
from fugue_tpu.jax import JaxExecutionEngine

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _write_part(src: str, i: int, n: int = 900, seed=None, lo=0, hi=12, nulls=False):
    rng = np.random.default_rng(1000 + i if seed is None else seed)
    v = rng.integers(0, 100, n).astype("float64")
    if nulls:
        v[rng.random(n) < 0.1] = np.nan
    pq.write_table(
        pa.table(
            {
                "k": rng.integers(lo, hi, n).astype("int64"),
                "v": v,
                "w": rng.integers(0, 50, n).astype("int64"),
            }
        ),
        os.path.join(src, f"part_{i:03d}.parquet"),
    )


def _src_dir(tmp_path, name="src", files=3, **kw) -> str:
    src = str(tmp_path / name)
    os.makedirs(src, exist_ok=True)
    for i in range(files):
        _write_part(src, i, **kw)
    return src


BUILDS = {
    "chain": lambda dag, src: (
        dag.load(src, fmt="parquet")
        .filter(col("v") > 10)
        .select(col("k"), (col("v") * 2).alias("x"), col("w"))
        .yield_dataframe_as("r", as_local=True)
    ),
    "filter": lambda dag, src: (
        dag.load(src, fmt="parquet")
        .filter(col("v") > 50)
        .yield_dataframe_as("r", as_local=True)
    ),
    "agg": lambda dag, src: (
        dag.load(src, fmt="parquet")
        .filter(col("v") > 10)
        .partition_by("k")
        .aggregate(
            ff.sum(col("v")).alias("s"),
            ff.count(col("v")).alias("n"),
            ff.avg(col("v")).alias("m"),
            ff.min(col("v")).alias("lo"),
            ff.max(col("v")).alias("hi"),
        )
        .yield_dataframe_as("r", as_local=True)
    ),
}


def _run(build, src, conf, engine_cls=JaxExecutionEngine, engine=None):
    eng = engine if engine is not None else engine_cls(conf)
    dag = FugueWorkflow()
    build(dag, src)
    dag.run(eng)
    return dag.yields["r"].result.as_pandas(), eng, dag


def _stats(eng):
    return eng.stats()["cache"]


def _delta_cycle(build, src, conf, engine_cls, grow):
    """cold -> grow -> warm (must be a delta partial hit) -> cache-off
    reference; warm must equal the reference BIT-FOR-BIT."""
    off = dict(conf)
    off[FUGUE_TPU_CONF_CACHE_ENABLED] = False
    cold, _, _ = _run(build, src, conf, engine_cls)
    grow()
    warm, we, wdag = _run(build, src, conf, engine_cls)
    ref, _, _ = _run(build, src, off, engine_cls)
    st = _stats(we)
    assert st["partial_hits"] >= 1, st
    assert st["delta_partitions_fresh"] >= 1, st
    assert st["bytes_skipped_delta"] > 0, st
    pd.testing.assert_frame_equal(warm, ref)
    return warm, we, wdag


# ---------------------------------------------------------------------------
# the delta parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", ["chain", "filter", "agg"])
@pytest.mark.parametrize("engine_cls", [JaxExecutionEngine, NativeExecutionEngine])
@pytest.mark.parametrize("opt", [True, False])
def test_delta_parity(tmp_path, shape, engine_cls, opt):
    src = _src_dir(tmp_path)
    conf = {
        FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache"),
        FUGUE_TPU_CONF_PLAN_OPTIMIZE: opt,
    }
    _delta_cycle(
        BUILDS[shape], src, conf, engine_cls, lambda: _write_part(src, 3)
    )


@pytest.mark.parametrize("engine_cls", [JaxExecutionEngine, NativeExecutionEngine])
def test_delta_aggregate_nulls_and_new_keys(tmp_path, engine_cls):
    """NULL values exercise the merge-identity semantics (an all-NULL
    group's sum stays NULL, avg recomposes as sum/count); the delta
    partition introduces keys the cached partial has never seen."""
    src = _src_dir(tmp_path, nulls=True)
    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}
    _delta_cycle(
        BUILDS["agg"],
        src,
        conf,
        engine_cls,
        lambda: _write_part(src, 3, lo=12, hi=16, nulls=True),
    )


def test_delta_multi_generation(tmp_path):
    """Append twice: the second warm run consumes the manifest the first
    one republished (multi-segment / re-published partial)."""
    src = _src_dir(tmp_path)
    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}
    for shape in ("chain", "agg"):
        sub = _src_dir(tmp_path, name=f"src_{shape}")
        _run(BUILDS[shape], sub, conf)
        _write_part(sub, 3)
        _run(BUILDS[shape], sub, conf)
        _write_part(sub, 4)
        warm, we, _ = _run(BUILDS[shape], sub, conf)
        off = dict(conf)
        off[FUGUE_TPU_CONF_CACHE_ENABLED] = False
        ref, _, _ = _run(BUILDS[shape], sub, off)
        pd.testing.assert_frame_equal(warm, ref)
        assert _stats(we)["partial_hits"] >= 1


def test_grown_csv_single_file(tmp_path):
    """An appended-to csv with an unchanged prefix: the stored digest +
    row count prove the append, and only the appended rows recompute."""
    f = str(tmp_path / "data.csv")
    rng = np.random.default_rng(7)

    def append(n):
        pdf = pd.DataFrame(
            {"k": rng.integers(0, 8, n), "v": rng.integers(0, 50, n)}
        )
        pdf.to_csv(
            f, mode="a" if os.path.exists(f) else "w", header=False, index=False
        )

    append(2500)

    def build(dag, src):
        (
            dag.load(src, fmt="csv", columns="k:long,v:double", header=False)
            .filter(col("v") > 5)
            .partition_by("k")
            .aggregate(ff.sum(col("v")).alias("s"), ff.avg(col("v")).alias("m"))
            .yield_dataframe_as("r", as_local=True)
        )

    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}
    _, we, _ = _delta_cycle(build, f, conf, JaxExecutionEngine, lambda: append(40))
    # the skipped bytes are the old file prefix
    assert _stats(we)["bytes_skipped_delta"] > 0


# ---------------------------------------------------------------------------
# the refusal ladder — every refusal degrades to whole-task semantics
# ---------------------------------------------------------------------------


def _refusal_case(tmp_path, mutate, expect_reason):
    """cold -> mutate source -> warm must NOT delta-serve, must equal the
    cache-off reference, and the reason must render in explain()."""
    src = _src_dir(tmp_path)
    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}
    build = BUILDS["agg"]
    _run(build, src, conf)
    mutate(src)
    # dry-run explain BEFORE the warm run consults the live store
    probe = JaxExecutionEngine(conf)
    dag = FugueWorkflow()
    build(dag, src)
    exp = dag.explain(engine=probe)
    assert expect_reason in exp, exp
    warm, we, _ = _run(build, src, conf, engine=probe)
    off = dict(conf)
    off[FUGUE_TPU_CONF_CACHE_ENABLED] = False
    ref, _, _ = _run(build, src, off)
    pd.testing.assert_frame_equal(warm, ref)
    st = _stats(we)
    assert st["partial_hits"] == 0, st
    assert st["delta_refusals"] >= 1, st


def test_changed_partition_contents_refuses(tmp_path):
    def mutate(src):
        _write_part(src, 1, seed=999)  # REWRITE partition 1 (not an append)

    _refusal_case(tmp_path, mutate, "partition contents changed (not an append)")


def test_new_partition_sorting_before_cached_refuses(tmp_path):
    def mutate(src):
        rng = np.random.default_rng(5)
        pq.write_table(
            pa.table(
                {
                    "k": rng.integers(0, 12, 500).astype("int64"),
                    "v": rng.integers(0, 100, 500).astype("float64"),
                    "w": rng.integers(0, 50, 500).astype("int64"),
                }
            ),
            os.path.join(src, "aaa_first.parquet"),  # sorts before part_*
        )

    _refusal_case(tmp_path, mutate, "partition order changed")


def test_deleted_partition_refuses(tmp_path):
    def mutate(src):
        os.remove(os.path.join(src, "part_001.parquet"))

    _refusal_case(tmp_path, mutate, "cached partitions missing from source")


def test_non_row_local_verb_refuses_but_load_still_deltas(tmp_path):
    """A distinct in the chain has no delta form — but the LOAD beneath
    it is still delta-served, so the expensive decode of old partitions
    is skipped even when the consumer recomputes."""
    src = _src_dir(tmp_path)
    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}

    def build(dag, s):
        (
            dag.load(s, fmt="parquet")
            .filter(col("v") > 10)
            .distinct()
            .yield_dataframe_as("r", as_local=True)
        )

    _run(build, src, conf)
    _write_part(src, 3)
    probe = JaxExecutionEngine(conf)
    dag = FugueWorkflow()
    build(dag, src)
    exp = dag.explain(engine=probe)
    assert "not row-local" in exp or "not incrementally maintainable" in exp, exp
    assert "DELTA[" in exp, exp  # the Load's own partial hit
    warm, we, _ = _run(build, src, conf, engine=probe)
    off = dict(conf)
    off[FUGUE_TPU_CONF_CACHE_ENABLED] = False
    ref, _, _ = _run(build, src, off)
    pd.testing.assert_frame_equal(warm, ref)
    st = _stats(we)
    assert st["partial_hits"] >= 1  # the load
    assert st["delta_partitions"] == 3


def test_edited_udf_downstream_recomputes_correctly(tmp_path):
    """An (edited) UDF transformer is never delta-eligible; the run still
    serves the Load's delta and recomputes the transform correctly."""
    src = _src_dir(tmp_path)
    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}

    def make(mult):
        ns = {"pd": pd}
        exec(
            "def scale(df: pd.DataFrame) -> pd.DataFrame:\n"
            f"    return df.assign(v=df['v'] * {mult}.0)\n",
            ns,
        )
        return ns["scale"]

    def build_with(udf):
        def build(dag, s):
            (
                dag.load(s, fmt="parquet")
                .transform(udf, schema="*")
                .yield_dataframe_as("r", as_local=True)
            )

        return build

    _run(build_with(make(2)), src, conf)
    _write_part(src, 3)
    warm, we, _ = _run(build_with(make(3)), src, conf)  # EDITED udf
    off = dict(conf)
    off[FUGUE_TPU_CONF_CACHE_ENABLED] = False
    ref, _, _ = _run(build_with(make(3)), src, off)
    pd.testing.assert_frame_equal(warm, ref)
    assert _stats(we)["partial_hits"] >= 1  # the load's delta


def test_stream_input_refuses_delta(tmp_path):
    """A one-pass stream source refuses to fingerprint at all — the delta
    layer inherits the poisoned subtree and the run stays correct."""
    from fugue_tpu.dataframe import (
        ArrowDataFrame,
        LocalDataFrameIterableDataFrame,
    )

    pdf = pd.DataFrame(
        {"k": np.arange(2000) % 7, "v": np.arange(2000, dtype="float64")}
    )
    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}

    def stream():
        tbl = pa.Table.from_pandas(pdf, preserve_index=False)
        return LocalDataFrameIterableDataFrame(
            (ArrowDataFrame(tbl.slice(s, 500)) for s in range(0, 2000, 500)),
            schema=ArrowDataFrame(tbl).schema,
        )

    def build(dag, _s):
        (
            dag.df(stream())
            .partition_by("k")
            .aggregate(ff.sum(col("v")).alias("s"))
            .yield_dataframe_as("r", as_local=True)
        )

    r1, e1, _ = _run(build, None, conf)
    r2, e2, _ = _run(build, None, conf)
    assert _stats(e2)["partial_hits"] == 0
    pd.testing.assert_frame_equal(
        r1.sort_values("k").reset_index(drop=True),
        r2.sort_values("k").reset_index(drop=True),
    )


def test_delta_disabled_conf_gate(tmp_path):
    src = _src_dir(tmp_path)
    conf = {
        FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache"),
        FUGUE_TPU_CONF_CACHE_DELTA_ENABLED: False,
    }
    _run(BUILDS["agg"], src, conf)
    _write_part(src, 3)
    warm, we, _ = _run(BUILDS["agg"], src, conf)
    off = dict(conf)
    off[FUGUE_TPU_CONF_CACHE_ENABLED] = False
    ref, _, _ = _run(BUILDS["agg"], src, off)
    pd.testing.assert_frame_equal(warm, ref)
    st = _stats(we)
    assert st["partial_hits"] == 0 and st["manifest_publishes"] == 0


# ---------------------------------------------------------------------------
# store consistency: entry-count eviction and stale manifests
# ---------------------------------------------------------------------------


def test_disk_max_entries_evicts_lru(tmp_path):
    """The artifact store honors the COUNT cap alongside the byte cap,
    evicting oldest-mtime first, meta sidecars included."""
    import time

    from fugue_tpu.cache.store import ArtifactStore

    store = ArtifactStore(str(tmp_path / "store"), cap_bytes=0, cap_entries=2)
    eng = NativeExecutionEngine({})
    from fugue_tpu.dataframe import PandasDataFrame

    for i, fp in enumerate(["fp_a", "fp_b", "fp_c"]):
        df = PandasDataFrame(pd.DataFrame({"x": [i]}), "x:long")
        store.publish(fp, df, eng, "x:long")
        t = 1_000_000 + i  # deterministic mtime order
        os.utime(store._obj(fp), (t, t))
    assert store.evict_to_cap() == 1
    left = {f for f in os.listdir(store.objs) if f.endswith(".parquet")}
    assert left == {"fp_b.parquet", "fp_c.parquet"}
    assert not os.path.exists(store._meta("fp_a"))


def test_evicted_partition_artifact_invalidates_only_its_manifest(tmp_path):
    """Delete one chain's partial artifact: that chain degrades to a
    whole-task recompute (stale manifest self-deletes), while the OTHER
    chain keeps delta-serving — eviction never poisons the whole cache."""
    src_a = _src_dir(tmp_path, name="src_a")
    src_b = _src_dir(tmp_path, name="src_b")
    d = str(tmp_path / "cache")
    conf = {FUGUE_TPU_CONF_CACHE_DIR: d}
    _run(BUILDS["agg"], src_a, conf)
    _run(BUILDS["chain"], src_b, conf)
    _write_part(src_a, 3)
    _write_part(src_b, 3)
    # find chain A's manifest and delete the artifact it references
    import json

    manifests = os.path.join(d, "manifests")
    acc = [
        (f, json.load(open(os.path.join(manifests, f))))
        for f in os.listdir(manifests)
    ]
    victims = [(f, m) for f, m in acc if m["mode"] == "acc"]
    assert victims
    vf, vm = victims[0]
    os.remove(os.path.join(d, "objs", vm["partial"]["artifact"] + ".parquet"))
    warm_a, ea, _ = _run(BUILDS["agg"], src_a, conf)
    warm_b, eb, _ = _run(BUILDS["chain"], src_b, conf)
    off = dict(conf)
    off[FUGUE_TPU_CONF_CACHE_ENABLED] = False
    ref_a, _, _ = _run(BUILDS["agg"], src_a, off)
    ref_b, _, _ = _run(BUILDS["chain"], src_b, off)
    pd.testing.assert_frame_equal(warm_a, ref_a)
    pd.testing.assert_frame_equal(warm_b, ref_b)
    # the aggregate's manifest could not apply (refusal counted); the
    # LOAD beneath it — and all of chain B — still delta-serve: losing
    # one artifact never poisons the rest of the cache
    assert _stats(ea)["delta_refusals"] >= 1
    assert _stats(eb)["partial_hits"] >= 1
    # the stale manifest deleted itself mid-run and the recompute then
    # REPUBLISHED a consistent one: it now covers the grown partition
    # set and references an artifact that actually exists
    m2 = json.load(open(os.path.join(manifests, vf)))
    assert len(m2["partitions"]) == 4
    assert os.path.exists(
        os.path.join(d, "objs", m2["partial"]["artifact"] + ".parquet")
    )


def test_runtime_failure_falls_back_to_full_recompute(tmp_path, monkeypatch):
    """A delta recompute that blows up mid-run (source mutated between
    plan and execution, schema drift...) degrades IN PLACE to a full
    recompute from the source — never an error, never wrong data."""
    import fugue_tpu.cache.delta as delta_mod

    src = _src_dir(tmp_path)
    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}
    _run(BUILDS["agg"], src, conf)
    _write_part(src, 3)

    def boom(engine, hit):
        raise RuntimeError("injected delta failure")

    monkeypatch.setattr(delta_mod, "_load_fresh", boom)
    warm, we, _ = _run(BUILDS["agg"], src, conf)
    monkeypatch.undo()
    off = dict(conf)
    off[FUGUE_TPU_CONF_CACHE_ENABLED] = False
    ref, _, _ = _run(BUILDS["agg"], src, off)
    pd.testing.assert_frame_equal(warm, ref)


# ---------------------------------------------------------------------------
# persist / restart and observability
# ---------------------------------------------------------------------------


def test_persist_delta_merged_survives_restart(tmp_path):
    """persist() of a delta-merged frame publishes the MERGED artifact:
    a later exact-match run on a FRESH engine (a restarted process)
    takes the fast whole-task disk hit, never re-entering delta."""
    src = _src_dir(tmp_path)
    d = str(tmp_path / "cache")
    conf = {FUGUE_TPU_CONF_CACHE_DIR: d}

    def build(dag, s):
        (
            dag.load(s, fmt="parquet")
            .filter(col("v") > 10)
            .partition_by("k")
            .aggregate(ff.sum(col("v")).alias("s"), ff.avg(col("v")).alias("m"))
            .persist()
            .yield_dataframe_as("r", as_local=True)
        )

    _run(build, src, conf)
    _write_part(src, 3)
    warm, we, _ = _run(build, src, conf)
    assert _stats(we)["partial_hits"] >= 1
    # "restart": a brand-new engine over the unchanged source must take
    # the whole-task hit for the merged fingerprint — zero delta work
    again, e3, _ = _run(build, src, conf)
    st = _stats(e3)
    assert st["hits_mem"] + st["hits_disk"] >= 1, st
    assert st["partial_hits"] == 0, st
    pd.testing.assert_frame_equal(warm, again)


def test_explain_renders_delta_partitions(tmp_path):
    src = _src_dir(tmp_path)
    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}
    _run(BUILDS["agg"], src, conf)
    _write_part(src, 3)
    probe = JaxExecutionEngine(conf)
    dag = FugueWorkflow()
    BUILDS["agg"](dag, src)
    exp = dag.explain(engine=probe)
    assert "DELTA[3/4 partitions]" in exp, exp
    # the optimizer marks eligible verbs
    assert "delta:source" in exp and "delta:accumulator" in exp, exp


def test_delta_counters_flatten_to_valid_prometheus(tmp_path):
    from fugue_tpu.obs import validate_prometheus_text
    from fugue_tpu.obs.prom import to_prometheus_text

    src = _src_dir(tmp_path)
    conf = {FUGUE_TPU_CONF_CACHE_DIR: str(tmp_path / "cache")}
    _run(BUILDS["agg"], src, conf)
    _write_part(src, 3)
    _, we, _ = _run(BUILDS["agg"], src, conf)
    text = to_prometheus_text(engine=we)
    validate_prometheus_text(text)
    for want in (
        "fugue_tpu_cache_partial_hits",
        "fugue_tpu_cache_delta_partitions",
        "fugue_tpu_cache_bytes_skipped_delta",
    ):
        assert want in text, want
    assert "fugue_tpu_cache_partial_hits 1" in text, text
