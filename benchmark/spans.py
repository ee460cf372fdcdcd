"""Sums over the program's span records (``fugue_tpu.obs`` tracer
records: ``name``, ``id``, ``parent``, ``ts`` and ``dur`` in ns) for the
per-layer readers under ``benchmark/metrics``."""

from typing import Any, Dict, Iterable, List, Optional, Tuple


def _union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def self_ns(spans: List[Dict[str, Any]], names: Any) -> Optional[int]:
    """The self time of every span whose name ``names(name)`` accepts: its
    duration less the union of its direct children's intervals, clipped to
    it. None when no span is accepted."""
    chosen = [s for s in spans if names(s["name"])]
    if not chosen:
        return None
    ids = {s["id"] for s in chosen}
    kids: Dict[str, List[Tuple[int, int]]] = {}
    for s in spans:
        if s.get("parent") in ids:
            kids.setdefault(s["parent"], []).append((s["ts"], s["ts"] + s["dur"]))
    total = 0
    for s in chosen:
        lo, hi = s["ts"], s["ts"] + s["dur"]
        inside = [(max(a, lo), min(b, hi)) for a, b in kids.get(s["id"], []) if b > lo and a < hi]
        total += s["dur"] - _union_ns(inside)
    return total


def outermost_ns(spans: List[Dict[str, Any]], name: str) -> Optional[int]:
    """The summed duration of the spans named ``name`` that have no
    ancestor of that name. None when there is none."""
    by_id = {s["id"]: s for s in spans}
    total, seen = 0, False
    for s in spans:
        if s["name"] != name:
            continue
        seen = True
        p = by_id.get(s.get("parent"))
        while p is not None and p["name"] != name:
            p = by_id.get(p.get("parent"))
        if p is None:
            total += s["dur"]
    return total if seen else None


def per_query_ms(run: Any, ns: Optional[int]) -> Optional[float]:
    """Milliseconds per completed query; None without a span or a query."""
    done = len(run.completed)
    return ns / done / 1e6 if ns is not None and done else None
