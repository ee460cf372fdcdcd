"""Counters of the host work on the query path: ``engine.stats()["host_ops"]``.

Fed from the tracer's closed records (``Tracer._emit``), so they count
exactly what the spans say, cost nothing while tracing is off, and read
zero unless tracing is on:

- ``host.op`` — each fallback of an engine verb into the host engine:
  ``calls`` and ``rows_in``, keyed ``{op: {reason: n}}``;
- ``frame.fetch`` — device columns read back to the host: ``fetches``,
  ``fetch_rows``, ``fetch_bytes``;
- ``frame.ingest`` — one frame encoded and put on the device:
  ``ingests``, ``ingest_rows``, ``ingest_bytes``.

One set per process, like the tracer; every engine mounts it.
"""

import threading
from typing import Any, Dict

__all__ = ["HostOpStats", "get_host_op_stats"]

# span name -> (count key, rows key, bytes key)
_MOVES = {
    "frame.fetch": ("fetches", "fetch_rows", "fetch_bytes"),
    "frame.ingest": ("ingests", "ingest_rows", "ingest_bytes"),
}


class HostOpStats:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._calls: Dict[str, Dict[str, int]] = {}
        self._rows_in: Dict[str, Dict[str, int]] = {}
        self._moves = {k: 0 for keys in _MOVES.values() for k in keys}

    def observe_record(self, rec: Dict[str, Any]) -> None:
        name = rec["name"]
        if name != "host.op" and name not in _MOVES:
            return
        args = rec.get("args") or {}
        with self._lock:
            if name == "host.op":
                op, reason = str(args.get("op")), str(args.get("reason"))
                calls = self._calls.setdefault(op, {})
                calls[reason] = calls.get(reason, 0) + 1
                rows = self._rows_in.setdefault(op, {})
                rows[reason] = rows.get(reason, 0) + int(args.get("rows_in", 0))
                return
            n, rows_key, bytes_key = _MOVES[name]
            self._moves[n] += 1
            self._moves[rows_key] += int(args.get("rows", 0))
            self._moves[bytes_key] += int(args.get("bytes", 0))

    def as_dict(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "calls": {k: dict(v) for k, v in self._calls.items()},
                "rows_in": {k: dict(v) for k, v in self._rows_in.items()},
                **self._moves,
            }

    def reset(self) -> None:
        with self._lock:
            self._calls.clear()
            self._rows_in.clear()
            for k in self._moves:
                self._moves[k] = 0


_HOST_OPS = HostOpStats()


def get_host_op_stats() -> HostOpStats:
    return _HOST_OPS
