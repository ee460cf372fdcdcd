"""The served cell ``resident.served2`` (TPC-H's throughput test, two
tenants through one ``EngineServer``): its CPU rehearsal at SF0.01 is
correct with answers from both streams and a value from each serve
reader, an answer built from the other stream's parameters reads
``correct`` false, and the served configuration's settings are those of
the server the cell runs."""

import argparse
import gc
import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

CELL = "resident.served2"
CONFIG = "tpch-sf1-served"
READERS = ["serve_key_ms.served", "serve_deliver_ms.served", "serve_overlap_share.served"]


def _run(cell, seed, trace=0, seconds=1.0):
    """A rehearsal of ``cell`` in this process on the CPU at SF0.01, and
    the view its readers read."""
    import jax

    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=seconds, trace=trace, rehearse_sf=0.01)
    run = harness.Run(cell, args, jax.devices()[:1], rehearse=True)
    captured = {}
    real = harness.RunView

    def keep(**kw):
        captured["view"] = real(**kw)
        return captured["view"]

    harness.RunView = keep
    try:
        out = run.go(0.0, harness.CompileCount())
    finally:
        harness.RunView = real
        run.close()
        # the run's engine and tables sit in reference cycles: free their
        # device arrays now, not in a later test that counts live arrays
        del run
        gc.collect()
    return out, captured["view"]


def test_served2_rehearsal_is_correct_from_both_streams_with_every_serve_reader():
    cell = harness.load_cell(CELL)
    assert cell.traffic["via"] == "server"
    assert cell.spec["per_layer"] == ["plan_ms.power", "device_idle_share.power"] + READERS
    out, view = _run(cell, seed=2**31 + 2601, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["checks"]) == {"q1_rel_err", "q3_rel_err"}
    assert {a.stream for a in view.completed} == {0, 1}
    assert {a.query for a in view.completed} == {"q1", "q3"}
    for m in ["plan_ms.power"] + READERS:
        v = cell.readers[m].read(view)
        assert v is not None and v >= 0, m
    # one serve.key and one serve.deliver a submission of the window
    names = [s["name"] for s in view.spans]
    assert names.count("serve.key") == names.count("serve.deliver") == len(view.answers)
    assert cell.readers["serve_overlap_share.served"].read(view) <= 100.0


def test_served_answer_from_the_other_streams_parameters_is_not_correct(monkeypatch):
    cell = harness.load_cell(CELL)
    out, _ = _run(cell, seed=2602)
    assert out["correct"] is True
    real = harness.ServerEntry.ask
    lock = threading.Lock()
    last = {}  # (stream, query) -> the parameters that stream asked last

    def swapped(self, stream, q, drawn):
        with lock:
            last[(stream, q.name)] = drawn
            other = last.get((1 - stream, q.name))
        return real(self, stream, q, drawn if other is None else other)

    monkeypatch.setattr(harness.ServerEntry, "ask", swapped)
    out, view = _run(cell, seed=2602)
    assert out["correct"] is False
    assert out["failed"] == 0  # every answer came, and some said the wrong thing
    assert {a.stream for a in view.answers} == {0, 1}


def test_served_config_is_the_server_the_cell_runs():
    """The configuration states the deployment (two streams, the server's
    defaults, no fleet, no journal); the engine it builds gives the
    cell's server exactly those settings."""
    import jax

    from fugue_tpu.jax import JaxExecutionEngine
    from fugue_tpu.parallel.mesh import build_mesh
    from fugue_tpu.serve import EngineServer

    cell = harness.load_cell(CELL)
    assert cell.spec["config"] == CONFIG
    cfg = cell.config
    assert cfg["streams"] == len(cell.traffic["streams"]) == 2
    args = argparse.Namespace(workload=CELL, seed=2603, seconds=1.0, trace=0, rehearse_sf=0.01)
    run = harness.Run(cell, args, jax.devices()[:1], rehearse=True)
    try:
        run.setup()
        server = run.entry.server
        assert (server.max_concurrent, server.queue_capacity) == (2, 64)
        assert server._fleet is None and server._journal is None
        assert run.eng.conf.get("fugue.tpu.cache.enabled", True) is False
        # the stated settings are the server's defaults: an engine without
        # them gives a server with the same
        plain = {k: v for k, v in cfg["engine_conf"].items() if not k.startswith("fugue.tpu.serve.")}
        bare_eng = JaxExecutionEngine(plain, mesh=build_mesh(devices=jax.devices()[:1]))
        try:
            with EngineServer(bare_eng) as bare:
                assert (bare.max_concurrent, bare.queue_capacity) == (2, 64)
                assert bare._fleet is None and bare._journal is None
        finally:
            bare_eng.stop_engine()
    finally:
        run.close()
        del run
        gc.collect()
