"""Multi-tenant serving layer (``fugue_tpu/serve``, docs/serving.md) —
ISSUE 10.

Covers admission (queue depth, tenant byte budgets), priority scheduling
with aging, tenant conf overlays and attribution, the liveness/readiness
split, the /serve/* RPC surface with idempotency keys, and the serve
stats/probe observability contract.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pandas as pd
import pytest

from fugue_tpu import FugueWorkflow
from fugue_tpu.column import col, functions as ff
from fugue_tpu.constants import (
    FUGUE_TPU_CONF_SERVE_DEFAULT_PRIORITY,
    FUGUE_TPU_CONF_SERVE_MAX_CONCURRENT,
    FUGUE_TPU_CONF_SERVE_QUEUE_DEPTH,
)
from fugue_tpu.execution import NativeExecutionEngine
from fugue_tpu.obs import get_sampler, get_span_metrics, get_tracer
from fugue_tpu.serve import (
    EngineServer,
    ServeHttpClient,
    ServeRejected,
    SubmissionCanceled,
    submission_key,
    tenant_policy,
)


def _agg_dag(seed: int = 0, rows: int = 64) -> FugueWorkflow:
    dag = FugueWorkflow()
    (
        dag.df(
            pd.DataFrame(
                {"k": [i % 4 for i in range(rows)], "v": [float(i + seed) for i in range(rows)]}
            )
        )
        .partition_by("k")
        .aggregate(ff.sum(col("v")).alias("s"), ff.count(col("v")).alias("n"))
        .yield_dataframe_as("r", as_local=True)
    )
    return dag


class _Gate:
    """A submission whose execution blocks until released — the knob that
    makes queue states deterministic in tests."""

    def __init__(self) -> None:
        self.release = threading.Event()
        self.entered = threading.Event()

    def dag(self) -> FugueWorkflow:
        gate = self

        def make() -> pd.DataFrame:
            gate.entered.set()
            assert gate.release.wait(30), "gate never released"
            return pd.DataFrame({"a": [1]})

        dag = FugueWorkflow()
        dag.create(make, schema="a:long").yield_dataframe_as("g", as_local=True)
        return dag


def test_submit_result_roundtrip():
    eng = NativeExecutionEngine()
    with EngineServer(eng) as srv:
        sub = srv.submit(_agg_dag(), tenant="t0")
        res = sub.result(timeout=60)
        df = res.yields["r"].result.as_pandas()
        assert sorted(df["n"]) == [16, 16, 16, 16]
        assert sub.status == "done" and sub.queue_wait_s is not None
    st = srv.stats()
    assert st["submitted"] == 1 and st["completed"] == 1 and st["failed"] == 0
    assert st["tenants"]["t0"]["completed"] == 1


def test_factory_and_built_dag_both_accepted():
    eng = NativeExecutionEngine()
    with EngineServer(eng) as srv:
        a = srv.submit(lambda: _agg_dag(seed=1), tenant="t0")
        b = srv.submit(_agg_dag(seed=2), tenant="t0")
        ra = a.result(timeout=60).yields["r"].result.as_pandas()
        rb = b.result(timeout=60).yields["r"].result.as_pandas()
        assert not ra.equals(rb)  # different seeds: genuinely distinct runs


def test_failed_run_raises_to_the_waiter_only():
    def boom() -> pd.DataFrame:
        raise RuntimeError("kaboom")

    eng = NativeExecutionEngine()
    with EngineServer(eng) as srv:
        bad = FugueWorkflow()
        bad.create(boom, schema="a:int").yield_dataframe_as("g", as_local=True)
        sub = srv.submit(bad)
        with pytest.raises(Exception, match="kaboom"):
            sub.result(timeout=60)
        assert sub.status == "failed"
        ok = srv.submit(_agg_dag())  # the server survives a failed run
        assert len(ok.result(timeout=60).yields["r"].result.as_pandas()) == 4
    st = srv.stats()
    assert st["failed"] == 1 and st["completed"] == 1


def test_queue_full_rejection_and_peak_depth():
    eng = NativeExecutionEngine(
        {
            FUGUE_TPU_CONF_SERVE_MAX_CONCURRENT: 1,
            FUGUE_TPU_CONF_SERVE_QUEUE_DEPTH: 1,
        }
    )
    gate = _Gate()
    with EngineServer(eng) as srv:
        blocker = srv.submit(gate.dag())
        assert gate.entered.wait(30)
        queued = srv.submit(_agg_dag(seed=1))
        with pytest.raises(ServeRejected) as ei:
            srv.submit(_agg_dag(seed=2))
        assert ei.value.reason == "queue_full"
        gate.release.set()
        blocker.result(timeout=60)
        queued.result(timeout=60)
    st = srv.stats()
    assert st["rejected_queue_full"] == 1
    assert st["peak_queue_depth"] == 1


def test_tenant_budget_gates_admission_and_releases_on_claim():
    eng = NativeExecutionEngine(
        {"fugue.tpu.serve.tenant.small.budget_bytes": 1000}
    )
    with EngineServer(eng) as srv:
        with pytest.raises(ServeRejected) as ei:
            srv.submit(_agg_dag(), tenant="small", reserve_bytes=2000)
        assert ei.value.reason == "tenant_budget"
        # within budget: admitted; after completion the charge is the
        # MEASURED result bytes; claiming the result releases it
        sub = srv.submit(_agg_dag(), tenant="small", reserve_bytes=900)
        sub.wait(60)
        charged = srv.stats()["charged_bytes"].get("small", 0)
        assert 0 < charged <= 1000  # restated to measured live bytes
        sub.result(timeout=60)
        assert srv.stats()["charged_bytes"].get("small", 0) == 0
        # other tenants were never gated
        free = srv.submit(_agg_dag(seed=5), tenant="big", reserve_bytes=10**9)
        free.result(timeout=60)
    assert srv.stats()["rejected_budget"] == 1


def test_priority_order_with_fifo_ties():
    eng = NativeExecutionEngine(
        {FUGUE_TPU_CONF_SERVE_MAX_CONCURRENT: 1, FUGUE_TPU_CONF_SERVE_DEFAULT_PRIORITY: 5}
    )
    gate = _Gate()
    order = []
    done = []
    with EngineServer(eng) as srv:
        blocker = srv.submit(gate.dag())
        assert gate.entered.wait(30)
        # queued while the worker is held: low-urgency first, then urgent
        low1 = srv.submit(_agg_dag(seed=1), priority=8)
        low2 = srv.submit(_agg_dag(seed=2), priority=8)
        hi = srv.submit(_agg_dag(seed=3), priority=1)
        gate.release.set()
        for name, sub in (("hi", hi), ("low1", low1), ("low2", low2), ("blocker", blocker)):
            sub.wait(60)
            done.append(name)
        # completion ORDER proof: started_at of the priority-1 run
        # precedes both priority-8 runs; FIFO within the tied pair
        t = {n: s._execution.started_at for n, s in
             (("low1", low1), ("low2", low2), ("hi", hi))}
        assert t["hi"] < t["low1"] < t["low2"], t
        order.append(t)


def test_aging_promotes_starved_low_priority():
    eng = NativeExecutionEngine(
        {
            FUGUE_TPU_CONF_SERVE_MAX_CONCURRENT: 1,
            "fugue.tpu.serve.aging_s": 0.05,
        }
    )
    gate = _Gate()
    with EngineServer(eng) as srv:
        blocker = srv.submit(gate.dag())
        assert gate.entered.wait(30)
        old_low = srv.submit(_agg_dag(seed=1), priority=9)
        time.sleep(0.6)  # ages >10 levels: beats any fresh priority-0
        fresh_hi = srv.submit(_agg_dag(seed=2), priority=0)
        gate.release.set()
        for s in (blocker, old_low, fresh_hi):
            s.wait(60)
        assert (
            old_low._execution.started_at < fresh_hi._execution.started_at
        ), "aged submission was starved by a fresh high-priority one"


def test_tenant_conf_overlay_plan_keys_only():
    eng = NativeExecutionEngine(
        {
            "fugue.tpu.serve.tenant.legacy.conf.fugue.tpu.plan.optimize": False,
            "fugue.tpu.serve.tenant.legacy.conf.fugue.workflow.concurrency": 4,
            "fugue.tpu.serve.tenant.legacy.priority": 2,
        }
    )
    pol = tenant_policy(eng.conf, "legacy")
    assert pol.priority == 2
    assert pol.conf_overlay == {"fugue.tpu.plan.optimize": False}
    assert pol.dropped_keys == ("fugue.workflow.concurrency",)
    with EngineServer(eng) as srv:
        dag = _agg_dag()
        sub = srv.submit(dag, tenant="legacy")
        sub.result(timeout=60)
        assert sub.priority == 2
        # the overlay landed on the workflow compile conf, and the run
        # honored it: the optimizer was off for this tenant's run
        assert dag._conf["fugue.tpu.plan.optimize"] is False
        assert dag.last_plan_report is not None
        assert not dag.last_plan_report.enabled
        # ...and did NOT leak into the shared engine conf
        assert "fugue.tpu.plan.optimize" not in eng.conf


def test_dedup_key_identity_and_refusal():
    eng = NativeExecutionEngine()
    k1 = submission_key(_agg_dag(seed=7), eng)
    k2 = submission_key(_agg_dag(seed=7), eng)
    k3 = submission_key(_agg_dag(seed=8), eng)
    assert k1 is not None and k1 == k2 and k1 != k3

    # a custom creator is "the outside world" to the fingerprinter
    # (docs/cache.md refusal ladder) => refused => NO dedup key: a
    # refusal can gate sharing off, never cause a wrong share
    def gen() -> pd.DataFrame:
        return pd.DataFrame({"a": [1]})

    dag = FugueWorkflow()
    dag.create(gen, schema="a:int").yield_dataframe_as("g", as_local=True)
    assert submission_key(dag, eng) is None


def test_serve_stats_mounted_on_engine_registry_and_probes():
    eng = NativeExecutionEngine()
    with EngineServer(eng) as srv:
        srv.submit(_agg_dag()).result(timeout=60)
        st = eng.stats()
        assert "serve" in st and st["serve"]["completed"] == 1
        names = get_sampler().probe_names()
        assert "serve_queue_depth" in names and "serve_active_runs" in names
        vals = get_sampler().sample_once()
        assert vals["serve_queue_depth"] == 0.0
        # keep-entries reset contract: counters zero, server state intact
        eng.reset_stats()
        assert eng.stats()["serve"]["completed"] == 0
        assert srv.running


def test_tenant_label_attribution_and_rotation():
    tr = get_tracer()
    sm = get_span_metrics()
    tr.clear()
    sm.clear()
    tr.enable()
    try:
        eng = NativeExecutionEngine()
        with EngineServer(eng) as srv:
            srv.submit(_agg_dag(), tenant="acme").result(timeout=60)
        series = sm.latency.series()
        acme = [lab for lab, _h in series if lab.get("tenant") == "acme"]
        assert acme, "no span-metric series carried the tenant label"
        # the run's own workflow/run labels nested INSIDE the tenant scope
        assert any(
            lab.get("span") == "workflow.run" and "run" in lab for lab in acme
        ), acme
        # bounded cardinality: > MAX_TENANT_SERIES distinct tenants rotate
        from fugue_tpu.obs.metrics import run_labels

        cap = sm.MAX_TENANT_SERIES
        for i in range(cap + 5):
            with run_labels(tenant=f"bulk{i}"), tr.span("serve.run"):
                pass
        tenants = {
            lab["tenant"]
            for lab, _h in sm.latency.series()
            if "tenant" in lab
        }
        assert len(tenants) <= cap
        assert "bulk0" not in tenants  # oldest rotated out
        assert f"bulk{cap + 4}" in tenants
    finally:
        tr.disable()
        tr.clear()
        sm.clear()


def test_stopped_server_rejects_and_drains():
    eng = NativeExecutionEngine({FUGUE_TPU_CONF_SERVE_MAX_CONCURRENT: 1})
    gate = _Gate()
    srv = EngineServer(eng).start()
    blocker = srv.submit(gate.dag())
    assert gate.entered.wait(30)
    queued = srv.submit(_agg_dag())
    t = threading.Thread(target=lambda: (time.sleep(0.2), gate.release.set()))
    t.start()
    srv.stop()
    t.join()
    blocker.wait(60)
    assert blocker.status == "done"
    with pytest.raises(ServeRejected):
        queued.result(timeout=5)  # drained: failed with server_stopped
    with pytest.raises(ServeRejected):
        srv.submit(_agg_dag())


# --------------------------------------------------------------------------
# the HTTP surface
# --------------------------------------------------------------------------


@pytest.fixture
def http_serve():
    eng = NativeExecutionEngine(
        {
            "fugue.rpc.server": "fugue_tpu.rpc.http.HttpRPCServer",
            FUGUE_TPU_CONF_SERVE_MAX_CONCURRENT: 1,
            FUGUE_TPU_CONF_SERVE_QUEUE_DEPTH: 2,
        }
    )
    rpc = eng.rpc_server
    rpc.start()
    srv = EngineServer(eng).start()
    rpc.bind_serve(srv)
    try:
        yield eng, rpc, srv
    finally:
        srv.stop()
        rpc.stop()


def _get(rpc, path):
    url = f"http://{rpc.host}:{rpc.port}{path}"
    try:
        with urllib.request.urlopen(url) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode() or "{}")


def test_rpc_submit_poll_result_cancel(http_serve):
    eng, rpc, srv = http_serve
    cl = ServeHttpClient(rpc.host, rpc.port)
    sub = cl.submit(lambda: _agg_dag(seed=3), tenant="acme")
    assert sub["tenant"] == "acme" and not sub["deduped"]
    frames = cl.result(sub["id"], timeout=60)
    assert sorted(frames["r"].columns) == ["k", "n", "s"]
    poll = cl.poll(sub["id"])
    assert poll["status"] == "done" and poll["run_s"] is not None
    # unknown id is a 404/KeyError, not a hang
    assert cl.poll("nope")["_http_status"] == 404
    with pytest.raises(KeyError):
        cl.result("nope")
    # cancel a queued submission behind a blocker
    gate = _Gate()
    blocker = srv.submit(gate.dag())
    assert gate.entered.wait(30)
    queued = cl.submit(lambda: _agg_dag(seed=4))
    out = cl.cancel(queued["id"])
    assert out["canceled"] is True and out["status"] == "canceled"
    gate.release.set()
    blocker.result(timeout=60)


def test_rpc_idempotency_key_replays_same_submission(http_serve):
    eng, rpc, srv = http_serve
    cl = ServeHttpClient(rpc.host, rpc.port)
    a = cl.submit(lambda: _agg_dag(seed=9), tenant="t", idempotency_key="job-1")
    b = cl.submit(lambda: _agg_dag(seed=9), tenant="t", idempotency_key="job-1")
    assert a["id"] == b["id"]
    assert srv.stats()["idempotent_replays"] == 1
    cl.result(a["id"], timeout=60)


def test_rpc_submit_rejection_is_429(http_serve):
    eng, rpc, srv = http_serve
    cl = ServeHttpClient(rpc.host, rpc.port)
    gate = _Gate()
    blocker = srv.submit(gate.dag())
    assert gate.entered.wait(30)
    subs = [cl.submit(lambda: _agg_dag(seed=s)) for s in (1, 2)]  # fills depth=2
    with pytest.raises(ServeRejected) as ei:
        cl.submit(lambda: _agg_dag(seed=3))
    assert ei.value.reason == "queue_full"
    gate.release.set()
    for s in subs:
        cl.result(s["id"], timeout=60)
    blocker.result(timeout=60)


def test_healthz_liveness_vs_readyz_readiness(http_serve):
    eng, rpc, srv = http_serve
    # liveness: the PRE-EXISTING contract, untouched and never load-aware
    code, live = _get(rpc, "/healthz")
    assert code == 200 and live["status"] == "ok" and "uptime_s" in live
    code, ready = _get(rpc, "/readyz")
    assert code == 200 and ready["status"] == "ready"
    assert ready["queue_capacity"] == 2 and ready["queue_free"] == 2
    # hold the worker and fill the queue: readiness flips 503, liveness not
    gate = _Gate()
    blocker = srv.submit(gate.dag())
    assert gate.entered.wait(30)
    subs = [srv.submit(_agg_dag(seed=s)) for s in (1, 2)]
    code, ready = _get(rpc, "/readyz")
    assert code == 503 and ready["status"] == "overloaded"
    assert ready["queue_free"] == 0
    code, live = _get(rpc, "/healthz")
    assert code == 200 and live["status"] == "ok"
    gate.release.set()
    blocker.result(timeout=60)
    for s in subs:
        s.result(timeout=60)
    code, ready = _get(rpc, "/readyz")
    assert code == 200 and ready["status"] == "ready"


def test_stats_endpoint_carries_serve_section(http_serve):
    eng, rpc, srv = http_serve
    srv.submit(_agg_dag()).result(timeout=60)
    code, st = _get(rpc, "/stats")
    assert code == 200
    assert st["serve"]["completed"] >= 1
    assert st["serve"]["queue_capacity"] == 2


def test_finished_submissions_release_device_frames():
    """A retained execution keeps its result, not its workflow's
    intermediate frames: four served joins leave the device as they found
    it (before the fix, each kept its joined frame alive — about 4x the
    fact table here, and an out-of-memory on the chip at 100M rows)."""
    import gc

    import jax
    import numpy as np

    from fugue_tpu.jax import JaxExecutionEngine

    def live() -> int:
        gc.collect()
        return sum(a.nbytes for a in jax.live_arrays())

    eng = JaxExecutionEngine({"fugue.tpu.cache.enabled": False})
    rng = np.random.default_rng(0)
    n = 50_000
    fact = eng.persist(
        eng.to_df(pd.DataFrame({"k": rng.integers(0, 100, n), "v": rng.random(n)}))
    )
    dim = eng.persist(
        eng.to_df(pd.DataFrame({"k": np.arange(100), "w": rng.random(100)}))
    )

    def factory() -> FugueWorkflow:
        dag = FugueWorkflow()
        (
            dag.df(fact)
            .inner_join(dag.df(dim), on=["k"])
            .select(col("k"), (col("v") * col("w")).alias("vw"))
            .partition_by("k")
            .aggregate(ff.sum(col("vw")).alias("s"))
            .yield_dataframe_as("r", as_local=True)
        )
        return dag

    before = live()
    with EngineServer(eng) as srv:
        subs = [srv.submit(factory, tenant=f"t{i % 2}") for i in range(4)]
        for s in subs:
            assert len(s.result(timeout=120).yields["r"].result.as_pandas()) == 100
        assert live() - before < n * 16 // 4
    eng.stop()
