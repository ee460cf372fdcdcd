"""The serve readers of ``resident.served2`` on hand-built span lists
whose answers are known, and their silence where the program opens no
such span (as at a commit before they were added)."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import load_module  # noqa: E402

MS = 1_000_000


def _reader(name):
    return load_module(os.path.join(ROOT, "benchmark", "metrics", f"{name}.py"))


def _span(name, t0, t1):
    return {"id": f"{name}@{t0}", "name": name, "ts": t0 * MS, "dur": (t1 - t0) * MS, "parent": None}


def _run(spans, queries=4, window_ms=1000):
    return SimpleNamespace(spans=spans, completed=[object()] * queries, window_s=window_ms / 1e3)


def _served():
    """Two tenants, four submissions; times in ms over a 1000 ms window."""
    return [
        _span("serve.key", 0, 2),
        _span("serve.key", 1, 4),
        _span("serve.run", 5, 405),  # tenant 0: Q1 then Q3
        _span("serve.run", 10, 110),  # tenant 1: Q3 then Q1
        _span("serve.deliver", 110, 111),
        _span("serve.key", 120, 121),
        _span("serve.run", 125, 600),
        _span("serve.deliver", 405, 408),
        _span("serve.key", 410, 412),
        _span("serve.run", 415, 500),
        _span("serve.deliver", 500, 502),
        _span("serve.deliver", 600, 602),
    ]


@pytest.mark.parametrize(
    "name,expected",
    [
        # (2 + 3 + 1 + 2) ms over 4 completed queries
        ("serve_key_ms.served", 8 / 4),
        # (1 + 3 + 2 + 2) / 4 spans
        ("serve_deliver_ms.served", 8 / 4),
        # two runs open over 10..110, 125..405 and 415..500: 465 of 1000 ms
        ("serve_overlap_share.served", 46.5),
    ],
)
def test_serve_reader_on_known_spans(name, expected):
    r = _reader(name)
    assert r.read(_run(_served())) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["serve_key_ms.served", "serve_deliver_ms.served", "serve_overlap_share.served"])
def test_serve_reader_is_silent_without_its_span(name):
    others = [s for s in _served() if s["name"] not in ("serve.key", "serve.deliver", "serve.run")]
    assert _reader(name).read(_run(others)) is None


def test_overlap_share_reads_zero_when_runs_take_turns():
    turns = [_span("serve.run", 0, 100), _span("serve.run", 100, 250), _span("serve.run", 300, 310)]
    assert _reader("serve_overlap_share.served").read(_run(turns)) == 0.0
