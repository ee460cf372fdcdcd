"""The per-layer readers of the query path's spans, on hand-built span
lists whose answers are known."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import load_module  # noqa: E402

MS = 1_000_000

READERS = [
    "parse_ms.power",
    "host_op_ms.power",
    "fetch_ms.power",
    "verb_self_ms.power",
    "read_ms.files",
    "frame_ingest_ms.files",
    "device_wait_ms.power",
]


def _reader(name):
    return load_module(os.path.join(ROOT, "benchmark", "metrics", f"{name}.py"))


def _span(sid, name, t0, t1, parent=None):
    return {"id": sid, "name": name, "ts": t0 * MS, "dur": (t1 - t0) * MS, "parent": parent}


def _run(spans, queries=2):
    return SimpleNamespace(spans=spans, completed=[object()] * queries)


def _two_queries():
    """Two queries' spans; times in ms."""
    return [
        # query 1
        _span("p1", "sql.parse", 0, 4),
        _span("p1b", "sql.parse", 1, 3, parent="p1"),  # nested: counted once
        _span("s1", "engine.select", 10, 110),
        _span("f1", "engine.filter", 12, 20, parent="s1"),
        _span("h1", "host.op", 20, 100, parent="s1"),
        _span("x1", "frame.fetch", 25, 45, parent="h1"),
        _span("t1", "engine.to_df", 40, 50, parent="h1"),  # overlaps the fetch
        _span("r1", "io.read", 0, 30),
        _span("i1", "frame.ingest", 11, 12, parent="s1"),
        # query 2
        _span("p2", "sql.parse", 200, 202),
        _span("s2", "engine.select", 210, 260),
        _span("h2", "host.op", 215, 255, parent="s2"),
        _span("x2", "frame.fetch", 220, 230, parent="h2"),
        _span("r2", "io.read", 190, 200),
        _span("i2", "frame.ingest", 205, 208),
        _span("j2", "engine.join", 300, 340),
        _span("w2", "device.wait", 310, 325, parent="j2"),
        _span("w2b", "device.wait", 330, 332, parent="j2"),
    ]


# per query, over the two queries above
EXPECTED = {
    # outer parses only: 4 + 2
    "parse_ms.power": (4 + 2) / 2,
    # host.op less the union of its children: 80 - (25..50 = 25), 40 - 10
    "host_op_ms.power": ((80 - 25) + (40 - 10)) / 2,
    "fetch_ms.power": (20 + 10) / 2,
    # engine.select less filter, host.op and ingest (11..12, 12..20, 20..100),
    # engine.filter itself, engine.to_df itself, the second select less its
    # host.op, the join less its two waits: (100 - 89) + 8 + 10 + (50 - 40)
    # + (40 - 17)
    "verb_self_ms.power": (11 + 8 + 10 + 10 + 23) / 2,
    "read_ms.files": (30 + 10) / 2,
    "frame_ingest_ms.files": (1 + 3) / 2,
    "device_wait_ms.power": (15 + 2) / 2,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_known_spans(name):
    assert _reader(name).read(_run(_two_queries())) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_divides_by_completed_queries(name):
    one, four = (_reader(name).read(_run(_two_queries(), q)) for q in (1, 4))
    assert one == pytest.approx(4 * four)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_its_span_reads_none(name):
    r = _reader(name)
    assert r.UNIT == "ms"
    other = [_span("w", "workflow.run", 0, 10)]
    assert r.read(_run(other)) is None
    assert r.read(_run(_two_queries(), queries=0)) is None


@pytest.mark.parametrize(
    "children, self_ms",
    [
        ([], 100),
        ([(10, 30), (20, 40)], 70),  # overlapping children
        ([(10, 30), (30, 50)], 60),  # touching children
        ([(-10, 20), (90, 130)], 70),  # children past the span are clipped
    ],
)
def test_self_time_takes_the_union_of_children(children, self_ms):
    spans = [_span("h", "host.op", 0, 100)]
    spans += [_span(f"c{i}", "frame.fetch", a, b, parent="h") for i, (a, b) in enumerate(children)]
    got = _reader("host_op_ms.power").read(_run(spans, 1))
    assert got == pytest.approx(self_ms)
