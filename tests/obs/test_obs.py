"""The unified observability subsystem (``fugue_tpu/obs``) — ISSUE 3.

Covers the satellite test checklist:

- span-tree shape for a transform+join+aggregate workflow;
- Chrome-trace export golden structure (Perfetto-loadable);
- disabled-path overhead guard: <2% of a small streaming aggregate's wall
  even if EVERY span call cost the measured worst case;
- fork-boundary round trip: worker spans and counter deltas recorded in a
  forked pool worker land in the driver tracer / registry;
- the MetricsRegistry lifecycle: stats()/reset_stats()/snapshot()/delta()
  and the legacy ``engine.*_stats`` shims.
"""

import json
import os
import time
from collections import Counter

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from fugue_tpu import FugueWorkflow
from fugue_tpu.collections import PartitionSpec
from fugue_tpu.column import col, functions as ff
from fugue_tpu.constants import (
    FUGUE_TPU_CONF_MAP_PARALLEL_MIN_ROWS,
    FUGUE_TPU_CONF_MAP_PARALLELISM,
    FUGUE_TPU_CONF_STREAM_CHUNK_ROWS,
    FUGUE_TPU_CONF_TRACE_ENABLED,
)
from fugue_tpu.dataframe import ArrowDataFrame, LocalDataFrameIterableDataFrame
from fugue_tpu.jax import JaxExecutionEngine
from fugue_tpu.obs import (
    MetricsRegistry,
    get_tracer,
    render_report,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)
from fugue_tpu.obs.tracer import NULL_SPAN


@pytest.fixture
def tracer():
    """Enabled tracer with a clean buffer; restores disabled+clear after."""
    tr = get_tracer()
    tr.clear()
    tr.enable()
    yield tr
    tr.disable()
    tr.clear()


def _frame(n=30_000, groups=64, seed=0):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {"k": rng.integers(0, groups, n), "v": rng.random(n)}
    )


def _stream(pdf: pd.DataFrame, step: int = 2048):
    tbl = pa.Table.from_pandas(pdf, preserve_index=False)
    return LocalDataFrameIterableDataFrame(
        (
            ArrowDataFrame(tbl.slice(s, min(step, tbl.num_rows - s)))
            for s in range(0, tbl.num_rows, step)
        ),
        schema=ArrowDataFrame(tbl).schema,
    )


def _ancestor_names(rec, by_id):
    names = []
    while rec is not None:
        names.append(rec["name"])
        rec = by_id.get(rec["parent"])
    return names


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_disabled_span_is_shared_null_object():
    tr = get_tracer()
    tr.disable()
    s1 = tr.span("x", rows=1)
    s2 = tr.span("y")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN
    with s1 as sp:
        sp.set(anything=1)  # no-op, no error
    assert tr.records() == [] or all(r["name"] not in ("x", "y") for r in tr.records())


def test_span_nesting_args_and_error(tracer):
    with tracer.span("outer", cat="t", a=1) as so:
        so.set(b=2)
        with tracer.span("inner", cat="t"):
            pass
        with pytest.raises(ValueError):
            with tracer.span("boom", cat="t"):
                raise ValueError("x")
    recs = {r["name"]: r for r in tracer.records()}
    assert recs["inner"]["parent"] == recs["outer"]["id"]
    assert recs["boom"]["parent"] == recs["outer"]["id"]
    assert recs["outer"]["args"] == {"a": 1, "b": 2}
    assert recs["boom"]["args"]["error"] == "ValueError"
    assert all(r["dur"] >= 0 and r["ts"] > 0 for r in recs.values())
    tree = tracer.span_tree()
    assert [n["name"] for n in tree] == ["outer"]
    assert sorted(c["name"] for c in tree[0]["children"]) == ["boom", "inner"]


def test_fork_boundary_protocol_mark_take_ingest(tracer):
    m = tracer.mark()
    with tracer.span("w1"):
        pass
    shipped = tracer.take_since(m)
    assert [r["name"] for r in shipped] == ["w1"]
    tracer.clear()
    tracer.ingest(shipped)
    assert [r["name"] for r in tracer.records()] == ["w1"]


# ---------------------------------------------------------------------------
# span tree over a real workflow: transform + join + aggregate
# ---------------------------------------------------------------------------


def test_span_tree_transform_join_aggregate(tracer):
    from typing import Dict

    import jax

    def tf(df: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return {"k": df["k"], "v": df["v"] + 1.0}

    pdf = _frame(4000, 16)
    dim = pd.DataFrame({"k": np.arange(16), "name": [f"g{i}" for i in range(16)]})
    e = JaxExecutionEngine({FUGUE_TPU_CONF_STREAM_CHUNK_ROWS: 1024})
    try:
        dag = FugueWorkflow()
        a = dag.df(pdf).transform(tf, schema="k:long,v:double")
        j = a.join(dag.df(dim), how="inner", on=["k"])
        agg = j.partition_by("k").aggregate(ff.sum(col("v")).alias("s"))
        agg.yield_dataframe_as("r", as_local=True)
        dag.run(e)
        assert len(dag.yields["r"].result.as_pandas()) == 16
    finally:
        e.stop_engine()
    recs = tracer.records()
    names = Counter(r["name"] for r in recs)
    assert names["workflow.run"] == 1
    assert names["workflow.task"] >= 4  # 2 creates + transform + join + agg
    assert names["engine.transform"] >= 1
    assert names["engine.join"] >= 1
    assert names["engine.aggregate"] >= 1
    by_id = {r["id"]: r for r in recs}
    # every engine verb span sits under a workflow task under the run
    for r in recs:
        if r["name"].startswith("engine."):
            chain = _ancestor_names(r, by_id)
            assert "workflow.task" in chain, chain
            assert chain[-1] == "workflow.run", chain


def test_span_tree_streaming_chunks_nest_in_verb(tracer):
    pdf = _frame(20_000, 32)
    e = JaxExecutionEngine({FUGUE_TPU_CONF_STREAM_CHUNK_ROWS: 2048})
    try:
        dag = FugueWorkflow()
        res = (
            dag.df(_stream(pdf))
            .partition_by("k")
            .aggregate(ff.sum(col("v")).alias("s"), ff.count(col("v")).alias("n"))
        )
        res.yield_dataframe_as("r", as_local=True)
        dag.run(e)
        assert len(dag.yields["r"].result.as_pandas()) == 32
    finally:
        e.stop_engine()
    recs = tracer.records()
    chunks = [r for r in recs if r["name"] == "stream.chunk"]
    assert len(chunks) >= 2
    by_id = {r["id"]: r for r in recs}
    chain = _ancestor_names(chunks[0], by_id)
    # the acceptance nesting: workflow task → engine verb → streaming chunk
    assert chain[0] == "stream.chunk"
    assert "engine.aggregate" in chain
    assert "workflow.task" in chain
    assert chain[-1] == "workflow.run"
    # rows/bytes in-out attributes ride the chunk spans
    assert all(c["args"].get("rows", 0) > 0 for c in chunks)
    assert sum(c["args"]["rows"] for c in chunks) == len(pdf)


# ---------------------------------------------------------------------------
# chrome trace export
# ---------------------------------------------------------------------------


def test_chrome_trace_export_golden(tracer, tmp_path):
    with tracer.span("workflow.task", cat="workflow", task="t0"):
        with tracer.span("engine.aggregate", cat="engine"):
            with tracer.span("stream.chunk", cat="stream", rows=10, chunk=0):
                pass
    doc = to_chrome_trace(tracer.records())
    # golden structure: the trace-event envelope Perfetto loads
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in evs] == [
        "stream.chunk",
        "engine.aggregate",
        "workflow.task",
    ]  # completion order
    for e in evs:
        # "id" rode in with ISSUE 18: the cluster-unique span id survives
        # export so cross-process assembly can dedup re-published spools
        assert set(e) == {
            "name", "cat", "ph", "ts", "dur", "pid", "tid", "args", "id",
        }
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
    chunk, agg, task = evs
    # nesting is encoded by time containment on one (pid, tid) track
    assert task["ts"] <= agg["ts"] and agg["ts"] <= chunk["ts"]
    assert agg["ts"] + agg["dur"] <= task["ts"] + task["dur"] + 1e-6
    assert chunk["args"] == {"rows": 10, "chunk": 0}
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta and meta[0]["args"]["name"].startswith("fugue-tpu")
    p = write_chrome_trace(str(tmp_path / "t.json"), tracer.records())
    with open(p) as f:
        assert json.load(f) == json.loads(json.dumps(doc))
    s = validate_chrome_trace(p)
    assert s["spans"] == 3 and "stream.chunk" in s["names"]


def test_validate_rejects_garbage(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"nope": []}))
    with pytest.raises(AssertionError):
        validate_chrome_trace(str(p))


def test_render_report_top_n(tracer):
    for _ in range(3):
        with tracer.span("engine.aggregate", cat="engine"):
            with tracer.span("stream.chunk", cat="stream"):
                pass
    txt = render_report(tracer.records(), {"resilience": {"a": 1}}, top_n=5)
    assert "engine.aggregate" in txt and "stream.chunk" in txt
    assert "[resilience]" in txt and "a: 1" in txt


# ---------------------------------------------------------------------------
# disabled-path overhead guard
# ---------------------------------------------------------------------------


def test_disabled_overhead_under_2_percent():
    """The <2% contract: run a small streaming aggregate with the tracer
    DISABLED and measure its wall; separately measure the worst-case cost
    of a disabled instrumented call site, and the number of spans the same
    run would record when enabled. Even charging every span at the
    measured per-call cost, the instrumentation budget must stay under 2%
    of the measured wall."""
    tr = get_tracer()
    tr.disable()
    tr.clear()
    pdf = _frame(30_000, 64, seed=1)
    aggs = lambda: [  # noqa: E731
        ff.sum(col("v")).alias("s"),
        ff.count(col("v")).alias("n"),
    ]
    spec = PartitionSpec(by=["k"])

    def run():
        e = JaxExecutionEngine({FUGUE_TPU_CONF_STREAM_CHUNK_ROWS: 2048})
        try:
            res = e.aggregate(_stream(pdf), spec, aggs())
            return len(res.as_pandas())
        finally:
            e.stop_engine()

    assert run() == 64  # warmup (compiles cached in-process)
    t0 = time.perf_counter()
    assert run() == 64
    wall_disabled = time.perf_counter() - t0

    # per-call cost of the disabled instrumented site
    n_calls = 100_000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        with tr.span("x", cat="engine", rows=1):
            pass
    per_call = (time.perf_counter() - t0) / n_calls

    # span count of the identical run when enabled
    tr.enable()
    try:
        tr.clear()
        assert run() == 64
        n_spans = len(tr.records())
    finally:
        tr.disable()
        tr.clear()
    assert n_spans > 0
    overhead = n_spans * per_call
    assert overhead < 0.02 * wall_disabled, (
        f"{n_spans} spans x {per_call * 1e6:.2f}µs = {overhead * 1e3:.3f}ms "
        f"vs wall {wall_disabled * 1e3:.1f}ms"
    )


# ---------------------------------------------------------------------------
# fork boundary: worker spans + counter deltas ship home
# ---------------------------------------------------------------------------


@pytest.mark.skipif(
    os.name != "posix", reason="fork pool requires posix fork"
)
def test_fork_worker_spans_and_counters_round_trip(tracer):
    from fugue_tpu.execution.parallel_map import fork_available

    if not fork_available():
        pytest.skip("no fork start method")
    import fugue_tpu.api as fa

    pdf = _frame(8000, 8, seed=2)

    def demean(df: pd.DataFrame) -> pd.DataFrame:
        df["v"] = df["v"] - df["v"].mean()
        return df

    e = JaxExecutionEngine(
        {
            FUGUE_TPU_CONF_MAP_PARALLELISM: 2,
            FUGUE_TPU_CONF_MAP_PARALLEL_MIN_ROWS: 0,
        }
    )
    try:
        out = fa.transform(
            pdf, demean, schema="*", partition=PartitionSpec(by=["k"]), engine=e
        )
        assert len(out) == len(pdf)
        recs = tracer.records()
        worker_chunks = [r for r in recs if r["name"] == "map.worker_chunk"]
        worker_parts = [r for r in recs if r["name"] == "map.partition"]
        assert worker_chunks, "no worker spans shipped home"
        driver_pid = os.getpid()
        assert all(r["pid"] != driver_pid for r in worker_chunks)
        # worker spans parent onto the driver's map.parallel span
        by_id = {r["id"]: r for r in recs}
        parallel = [r for r in recs if r["name"] == "map.parallel"]
        assert len(parallel) == 1 and parallel[0]["pid"] == driver_pid
        assert all(
            r["parent"] == parallel[0]["id"] for r in worker_chunks
        )
        assert all(
            by_id[r["parent"]]["name"] == "map.worker_chunk"
            for r in worker_parts
        )
        assert sum(r["args"]["rows_out"] for r in worker_parts) == len(pdf)
        # counter deltas merged into the driver registry
        rs = e.resilience_stats.as_dict()
        assert rs.get("map.worker_chunks", 0) >= 2
        assert rs.get("map.worker_partitions", 0) == 8
        assert rs.get("map.worker_rows_out", 0) == len(pdf)
        assert rs.get("map.chunks_ok", 0) >= 2
    finally:
        e.stop_engine()


# ---------------------------------------------------------------------------
# metrics registry + lifecycle + shims
# ---------------------------------------------------------------------------


def test_registry_unit():
    class Src:
        def __init__(self):
            self.n = 0

        def as_dict(self):
            return {"n": self.n, "nested": {"m": self.n * 2}, "tag": "x"}

        def reset(self):
            self.n = 0

    reg = MetricsRegistry()
    s = Src()
    reg.register("s", s)
    reg.register("lazy", lambda: s)
    before = reg.snapshot()
    s.n = 5
    d = reg.delta(before)
    assert d["s"] == {"n": 5, "nested": {"m": 10}, "tag": "x"}
    assert d["lazy"]["n"] == 5
    reg.reset()
    assert reg.as_dict()["s"]["n"] == 0


def test_engine_stats_surface_and_shims():
    from fugue_tpu.constants import FUGUE_TPU_CONF_STREAM_PREFETCH_DEPTH

    e = JaxExecutionEngine(
        {
            FUGUE_TPU_CONF_STREAM_CHUNK_ROWS: 2048,
            # force the prefetcher on so pipeline_stats records a run even
            # on a single-core host (whose adaptive default is serial)
            FUGUE_TPU_CONF_STREAM_PREFETCH_DEPTH: 2,
        }
    )
    try:
        st = e.stats()
        assert set(st) == {
            "resilience",
            "pipeline",
            "jit_cache",
            "plan",
            "analysis",
            "cache",
            "tuning",
            "shuffle",
            "host_ops",
            "latency",
            "telemetry",
        }
        # the deprecation shims delegate to the SAME objects the registry holds
        assert e.pipeline_stats is e.metrics.get("pipeline")
        assert e.resilience_stats is e.metrics.get("resilience")
        assert e.jit_cache_stats == e.metrics.get("jit_cache").as_dict()
        # exercise the engine, then prove one consistent reset
        pdf = _frame(6000, 8, seed=3)
        res = e.aggregate(
            _stream(pdf),
            PartitionSpec(by=["k"]),
            [ff.sum(col("v")).alias("s")],
        )
        assert len(res.as_pandas()) == 8
        st = e.stats()
        assert st["jit_cache"]["misses"] > 0
        assert st["pipeline"]["runs"] >= 1
        e.resilience_stats.inc("map.chunk_retries")
        before = e.metrics.snapshot()
        e.resilience_stats.inc("map.chunk_retries", 2)
        assert e.metrics.delta(before)["resilience"]["map.chunk_retries"] == 2
        e.reset_stats()
        st = e.stats()
        assert st["resilience"] == {}
        assert st["pipeline"]["runs"] == 0
        assert st["jit_cache"]["hits"] == 0 and st["jit_cache"]["misses"] == 0
        # compiled entries survive the reset by design (no forced recompiles)
        assert st["jit_cache"]["entries"] > 0
    finally:
        e.stop_engine()


def test_trace_conf_enables_and_env_overrides(monkeypatch):
    tr = get_tracer()
    tr.disable()
    e = JaxExecutionEngine({FUGUE_TPU_CONF_TRACE_ENABLED: True})
    try:
        assert tr.enabled
    finally:
        e.stop_engine()
        tr.disable()
    monkeypatch.setenv("FUGUE_TPU_TRACE", "0")
    e = JaxExecutionEngine({FUGUE_TPU_CONF_TRACE_ENABLED: True})
    try:
        assert not tr.enabled  # env wins over conf
    finally:
        e.stop_engine()
        tr.disable()
        tr.clear()


def test_workflow_trace_dir_auto_export(tmp_path, tracer):
    from fugue_tpu.constants import FUGUE_TPU_CONF_TRACE_DIR

    e = JaxExecutionEngine({FUGUE_TPU_CONF_TRACE_DIR: str(tmp_path)})
    try:
        dag = FugueWorkflow()
        dag.df(_frame(200, 4)).yield_dataframe_as("r", as_local=True)
        dag.run(e)
    finally:
        e.stop_engine()
    files = [f for f in os.listdir(tmp_path) if f.startswith("fugue_trace_")]
    assert len(files) == 1
    s = validate_chrome_trace(str(tmp_path / files[0]))
    assert "workflow.run" in s["names"]
