"""Two tenants' TPC-H Q1 and Q3 at once through one ``EngineServer`` at
the served configuration's settings (the server's defaults), over one
engine that holds SF0.01's tables on the device: every answer equals the
pandas reference, executions start while another runs, and ``serve.key``
and ``serve.deliver`` open once a submission. It guards the engine's
caches (the aggregate's helper columns, the dense kernel's programs, the
join's waits) under two threads."""

import gc
import os
import sys
import threading

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, tpch  # noqa: E402
from fugue_tpu.jax import JaxExecutionEngine  # noqa: E402
from fugue_tpu.obs import get_tracer  # noqa: E402
from fugue_tpu.parallel.mesh import build_mesh  # noqa: E402

SEED = 2_600_000_003
ROUNDS = 3
STREAMS = [["q1", "q3"], ["q3", "q1"]]


@pytest.fixture
def tracer():
    tr = get_tracer()
    tr.clear()
    tr.enable()
    yield tr
    tr.disable()
    tr.clear()


def test_two_tenants_q1_and_q3_at_once_match_the_reference(tracer):
    cfg = harness._read_json("configs", "tpch-sf1-served")
    tables = tpch.generate(0.01, SEED, cfg["resident"])
    eng = JaxExecutionEngine(dict(cfg["engine_conf"]), mesh=build_mesh(devices=jax.devices()[:1]))
    entry = None
    try:
        frames = {t: eng.persist(eng.to_df(tables[t])) for t in cfg["resident"]}
        entry = harness.ServerEntry(eng, frames, {})
        assert (entry.server.max_concurrent, entry.server.queue_capacity) == (2, 64)
        queries = {q: harness.load_query(q) for q in ("q1", "q3")}
        draw = harness.ParamDraw(queries, SEED)
        start = threading.Barrier(len(STREAMS))
        answers, errors = [], []

        def client(i):
            try:
                for _ in range(ROUNDS):
                    for q in STREAMS[i]:
                        start.wait(timeout=120)  # both streams submit at once
                        drawn = draw(q)
                        answers.append((q, drawn, entry.ask(i, queries[q], drawn)))
            except BaseException as e:  # noqa: B036 - reported below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(len(STREAMS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not errors, errors
        submitted = ROUNDS * sum(len(s) for s in STREAMS)
        assert len(answers) == submitted
        for q, drawn, got in answers:
            want = queries[q].reference.answer(tables, queries[q].bind(drawn), np.float64)
            err = queries[q].reference.compare(got, want)
            assert err is not None and err <= queries[q].limit, (q, drawn, err)

        st = eng.stats()["serve"]
        assert st["completed"] == submitted and st["dedup_hits"] == 0
        assert st["concurrent_starts"] > 0
        # the resident tables are device frames: no plan gets a dedup key
        assert st["key_refused"] == {"frame_without_digest": submitted}
        spans = tracer.records()
        for name in ("serve.submit", "serve.key", "serve.run", "serve.deliver"):
            assert sum(s["name"] == name for s in spans) == submitted, name
        keys = [s for s in spans if s["name"] == "serve.key"]
        assert all(s["args"]["refused"] == "frame_without_digest" for s in keys)
        runs = [s for s in spans if s["name"] == "serve.run"]
        assert any(s["args"]["concurrent"] == 1 for s in runs)
        deliver = [s for s in spans if s["name"] == "serve.deliver"]
        assert all(s["args"]["rows"] > 0 and s["args"]["bytes"] > 0 for s in deliver)
    finally:
        if entry is not None:
            entry.close()
        eng.stop_engine()
        # the engine and its frames sit in reference cycles: free their
        # device arrays now, not in a later test that counts live arrays
        del entry, eng
        gc.collect()
