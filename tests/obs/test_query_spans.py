"""The query path's spans and the ``host_ops`` counters.

A Q1-shaped query (filter, a two-key aggregate of expressions, ORDER BY),
a Q3-shaped one (two joins, a group-by on a renamed key, ORDER BY and
LIMIT) and a LOAD then SELECT run through FugueSQL on the JAX engine with
tracing on; each query's spans are read back as a tree. Each aggregate
runs on the device after one ``agg.project``; only the ORDER BY (and
Q3's LIMIT) run on the host.
"""

from collections import Counter
from datetime import date

import numpy as np
import pandas as pd
import pytest

from fugue_tpu.jax import JaxExecutionEngine
from fugue_tpu.obs import (
    get_host_op_stats,
    get_tracer,
    to_prometheus_text,
    validate_prometheus_text,
)
from fugue_tpu.sql import fugue_sql

Q1 = """
SELECT flag, status, SUM(qty) AS sum_qty, SUM(price * (1 - disc)) AS revenue,
  AVG(qty) AS avg_qty, COUNT(*) AS n
FROM items
WHERE shipdate <= CAST('1998-09-02' AS date)
GROUP BY flag, status
ORDER BY flag, status
"""

Q3 = """
SELECT o_key AS okey, SUM(price * (1 - disc)) AS revenue, o_date
FROM (SELECT c_key AS o_cust FROM customers WHERE segment = 'BUILDING') AS c
JOIN (SELECT o_key, o_cust, o_date FROM orders
      WHERE o_date < CAST('1995-03-15' AS date)) AS o ON c.o_cust = o.o_cust
JOIN (SELECT okey AS o_key, price, disc FROM items
      WHERE shipdate > CAST('1995-03-15' AS date)) AS l ON o.o_key = l.o_key
GROUP BY o_key, o_date
ORDER BY revenue DESC, o_date
LIMIT 10
"""

N_ITEMS = 4000

# the path a query takes from FugueSQL down to the host and the device
QUERY_PATH = {
    "sql.parse",
    "plan.optimize",
    "workflow.run",
    "workflow.task",
    "engine.select",
    "engine.join",
    "agg.project",
    "device.wait",
    "host.op",
    "frame.fetch",
    "frame.ingest",
    "io.read",
}


def _tables():
    rng = np.random.default_rng(7)
    days = pd.to_datetime("1992-01-01") + pd.to_timedelta(
        rng.integers(0, 2500, N_ITEMS), unit="D"
    )
    items = pd.DataFrame(
        {
            "okey": rng.integers(0, 500, N_ITEMS),
            "flag": rng.choice(["A", "N", "R"], N_ITEMS),
            "status": rng.choice(["F", "O"], N_ITEMS),
            "qty": rng.integers(1, 51, N_ITEMS).astype(float),
            "price": rng.random(N_ITEMS) * 1000,
            "disc": rng.integers(0, 11, N_ITEMS) / 100,
            "shipdate": [d.date() for d in days],
        }
    )
    orders = pd.DataFrame(
        {
            "o_key": np.arange(500),
            "o_cust": rng.integers(0, 50, 500),
            "o_date": [date(1995, 1, 1) + pd.Timedelta(days=int(d)) for d in rng.integers(0, 150, 500)],
        }
    )
    customers = pd.DataFrame(
        {
            "c_key": np.arange(50),
            "segment": rng.choice(["BUILDING", "MACHINERY"], 50),
        }
    )
    return {"items": items, "orders": orders, "customers": customers}


@pytest.fixture(scope="module")
def engine():
    eng = JaxExecutionEngine({"fugue.tpu.cache.enabled": False})
    yield eng
    eng.stop_engine()


@pytest.fixture
def tracer():
    tr = get_tracer()
    tr.clear()
    tr.enable()
    yield tr
    tr.disable()
    tr.clear()


def _ask(engine, kind, tmp_path):
    """Run one query; every frame reaches the engine as pandas, so its
    ingest happens inside the query."""
    t = _tables()
    if kind == "q1":
        return fugue_sql(Q1, engine=engine, as_fugue=True, items=t["items"])
    if kind == "q3":
        return fugue_sql(Q3, engine=engine, as_fugue=True, **t)
    path = str(tmp_path / "items.parquet")
    t["items"].to_parquet(path, index=False)
    return fugue_sql(f'items = LOAD "{path}"\n' + Q1, engine=engine, as_fugue=True)


def _by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r["name"], []).append(r)
    return out


def _parent(rec, recs):
    return next((r for r in recs if r["id"] == rec["parent"]), None)


KINDS = ["q1", "q3", "load"]


def _ancestors(rec, recs):
    out = []
    p = _parent(rec, recs)
    while p is not None:
        out.append(p)
        p = _parent(p, recs)
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_span_tree(engine, tracer, tmp_path, kind):
    _ask(engine, kind, tmp_path)
    recs = tracer.records()
    names = _by_name(recs)
    assert all(p["args"]["chars"] > 0 for p in names["sql.parse"])
    # the select's aggregate runs on the device: no host select
    assert not any(h["args"]["op"] in ("select", "aggregate") for h in names["host.op"])
    assert not any(h["args"]["reason"].startswith("agg_plan_refused") for h in names["host.op"])
    # one projection makes the expression argument's helper column
    (proj,) = names["agg.project"]
    assert proj["args"]["args"] == ["__agg_arg0"] and proj["args"]["keys"] == []
    sel = _parent(proj, recs)
    assert sel["name"] == "engine.select"
    # the select's input is not read back from the device
    assert not any(f["args"]["rows"] == N_ITEMS for f in names.get("frame.fetch", []))
    assert not any(sel in _ancestors(f, recs) for f in names.get("frame.fetch", []))
    # the ORDER BY sorts on the host
    assert any(
        h["args"]["op"] == "sort" and h["args"]["reason"] == "order_by"
        for h in names["host.op"]
    )
    # the items frame is ingested once, lazily, inside a verb
    ingests = [i for i in names["frame.ingest"] if i["args"]["rows"] == N_ITEMS]
    assert len(ingests) == 1
    assert ingests[0]["args"]["bytes"] > 0
    assert _parent(ingests[0], recs)["name"].startswith("engine.")
    if kind == "load":
        (read,) = names["io.read"]
        assert read["args"]["rows"] == N_ITEMS and read["args"]["bytes"] > 0
        assert _parent(read, recs)["name"] == "workflow.task"
    else:
        assert "io.read" not in names


@pytest.mark.parametrize("kind", KINDS)
def test_one_trace_id_per_query(engine, tracer, tmp_path, kind):
    _ask(engine, kind, tmp_path)
    recs = tracer.records()
    ids = {r.get("trace") for r in recs}
    assert len(ids) == 1 and None not in ids
    assert {"sql.parse", "plan.optimize", "workflow.run"} <= {r["name"] for r in recs}


class _Recording:
    """Stands in for ``jax.profiler.TraceAnnotation``."""

    entered = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Recording.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("kind", KINDS)
def test_query_path_spans_enter_the_annotation(engine, tracer, tmp_path, kind):
    saved = tracer._ann_cls
    tracer._ann_cls = _Recording
    _Recording.entered = []
    try:
        _ask(engine, kind, tmp_path)
    finally:
        tracer._ann_cls = saved
    recorded = Counter(r["name"] for r in tracer.records() if r["name"] in QUERY_PATH)
    assert recorded["agg.project"] == 1 and recorded["host.op"] >= 1
    annotated = Counter(n for n in _Recording.entered if n in QUERY_PATH)
    assert annotated == recorded


@pytest.mark.parametrize("kind", ["q1", "q3"])
def test_host_ops_counters_match_the_spans(engine, tracer, tmp_path, kind):
    stats = get_host_op_stats()
    stats.reset()
    _ask(engine, kind, tmp_path)
    recs = tracer.records()
    calls, rows = {}, {}
    for r in recs:
        if r["name"] == "host.op":
            key = (r["args"]["op"], r["args"]["reason"])
            calls[key] = calls.get(key, 0) + 1
            rows[key] = rows.get(key, 0) + r["args"]["rows_in"]
    got = engine.stats()["host_ops"]
    assert {(o, why): n for o, d in got["calls"].items() for why, n in d.items()} == calls
    assert {(o, why): n for o, d in got["rows_in"].items() for why, n in d.items()} == rows
    for span, prefix, count in (("frame.fetch", "fetch", "fetches"), ("frame.ingest", "ingest", "ingests")):
        mine = [r for r in recs if r["name"] == span]
        assert got[count] == len(mine)
        assert got[f"{prefix}_rows"] == sum(r["args"]["rows"] for r in mine)
        assert got[f"{prefix}_bytes"] == sum(r["args"]["bytes"] for r in mine)
    # the items frame is ingested; the aggregate's few rows reach the
    # ORDER BY before any ingest, so nothing is fetched back
    assert got["ingests"] > 0 and got["fetches"] == 0
    # the same counters on the /metrics page
    text = to_prometheus_text(engine=engine)
    validate_prometheus_text(text)
    assert "fugue_tpu_host_ops_calls_sort_order_by 1" in text.splitlines()


@pytest.mark.parametrize("kind", KINDS)
def test_tracing_off_records_and_counts_nothing(engine, tmp_path, kind):
    tr = get_tracer()
    tr.disable()
    tr.clear()
    before = get_host_op_stats().as_dict()
    res = _ask(engine, kind, tmp_path)
    assert len(res.as_pandas()) > 0
    assert tr.records() == []
    assert get_host_op_stats().as_dict() == before
