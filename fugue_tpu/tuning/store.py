"""Persistence for learned settings — the ``_tuned.json`` plan ledger.

The file is the SAME ``ops/_tuned.json`` the dense-sum kernel A/B has
always used; this module owns its ``"tuning"`` top-level key and
preserves every other key verbatim on publish, so the tenants of the
file never clobber each other. Layout::

    {
      "dense_sum": {...},            # ops/segment.py's A/B winner
      "tuning": {
        "version": 1,
        "plans": {
          "<plan_fp>": {
            "ts": <last-used epoch seconds>,
            "gen": <publish generation>,
            "streams": {"<sid>": {"chunk_rows", "prefetch_depth",
                                   "obs", "converged", "evidence"}},
            "joins":   {"<sid>": {"left_bytes", "right_bytes",
                                   "right_rows", "buckets", "obs",
                                   "converged", "evidence"}}
          }
        }
      }
    }

Contracts (docs/tuning.md):

- **Atomic publish**: temp-write in the same directory + ``os.replace``,
  the PR 1 checkpoint discipline — a reader (or a racing second process)
  sees the old complete file or the new complete file, never a torn one.
  Concurrent publishers re-read the file under their own process lock
  before merging, so a race loses at most the OTHER process's newest
  entry to last-writer-wins — never the file's integrity.
- **Corrupt/truncated/unreadable → defaults with ONE warning** per path
  per process; the store keeps working memory-only so a warm engine still
  converges within its own lifetime.
- **Stale-fingerprint eviction**: at most ``max_entries`` plan entries,
  least-recently-used (``ts``) dropped at publish time.
"""

import json
import logging
import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional, Set

__all__ = ["TunedStore", "default_tuned_path", "resolve_tuned_path"]

DEFAULT_MAX_ENTRIES = 64

_log = logging.getLogger("fugue_tpu.tuning")

# one warning per degraded path per process — corrupt files and unwritable
# directories must not spam every run
_WARNED: Set[str] = set()
_WARNED_LOCK = threading.Lock()


def _warn_once(path: str, kind: str, detail: str) -> None:
    key = f"{kind}:{path}"
    with _WARNED_LOCK:
        if key in _WARNED:
            return
        _WARNED.add(key)
    _log.warning(
        "tuning store %s (%s): %s -- degrading to defaults "
        "(static conf; in-memory learning only)",
        kind,
        path,
        detail,
    )


def default_tuned_path() -> str:
    from ..ops import segment as _seg

    return _seg._TUNED_PATH


def resolve_tuned_path(conf: Any) -> str:
    """Conf > env > package default (same precedence as the cache dir)."""
    from ..constants import FUGUE_TPU_CONF_TUNING_PATH

    try:
        p = str(conf.get(FUGUE_TPU_CONF_TUNING_PATH, "") or "")
    except Exception:
        p = ""
    if p:
        return p
    return os.environ.get("FUGUE_TPU_TUNING_PATH", "") or default_tuned_path()


class TunedStore:
    """mtime-cached reader + read-merge-write publisher over one path."""

    def __init__(
        self, path: str, max_entries: int = DEFAULT_MAX_ENTRIES, stats: Any = None
    ):
        self.path = path
        self.max_entries = max(1, int(max_entries))
        self._lock = threading.Lock()
        self._stats = stats
        # memory overlay: what THIS process learned; authoritative when the
        # file can't be read or written (degraded mode keeps converging)
        self._mem: Dict[str, Dict[str, Any]] = {}
        self._cache: Dict[str, Dict[str, Any]] = {}
        self._cache_sig: Any = ("", -1)

    def _inc(self, name: str, n: int = 1) -> None:
        if self._stats is not None:
            self._stats.inc(name, n)

    # -- reading -------------------------------------------------------------
    def _read_file(self) -> Dict[str, Any]:
        """The whole JSON document (all top-level keys), {} when absent or
        corrupt (corrupt warns once and counts a load_failure)."""
        try:
            with open(self.path, encoding="utf-8") as f:
                raw = f.read()
        except FileNotFoundError:
            return {}
        except OSError as ex:
            self._inc("load_failures")
            _warn_once(self.path, "unreadable", str(ex))
            return {}
        try:
            doc = json.loads(raw)
            if not isinstance(doc, dict):
                raise ValueError(f"top-level {type(doc).__name__}, expected object")
            return doc
        except Exception as ex:
            self._inc("load_failures")
            _warn_once(self.path, "corrupt", str(ex))
            return {}

    def _plans_of(self, doc: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
        tuning = doc.get("tuning")
        if not isinstance(tuning, dict):
            return {}
        plans = tuning.get("plans")
        if not isinstance(plans, dict):
            return {}
        # tolerate foreign/garbage entries: only dict-valued plans survive
        return {str(k): v for k, v in plans.items() if isinstance(v, dict)}

    def plans(self) -> Dict[str, Dict[str, Any]]:
        """All plan entries, file overlaid with this process's memory
        (memory wins — it is at least as new as what we last published)."""
        with self._lock:
            try:
                st = os.stat(self.path)
                sig = (self.path, st.st_mtime_ns, st.st_size)
            except OSError:
                sig = (self.path, -1, -1)
            if sig != self._cache_sig:
                self._cache = self._plans_of(self._read_file())
                self._cache_sig = sig
                self._inc("loads")
            merged = dict(self._cache)
            merged.update(self._mem)
            return merged

    def plan_entry(self, fp: str) -> Optional[Dict[str, Any]]:
        return self.plans().get(fp)

    def count(self) -> int:
        return len(self.plans())

    def remember(self, fp: str, entry: Dict[str, Any]) -> None:
        """In-memory-only update (observation bookkeeping on an already
        converged entry) — no file write, no eviction."""
        with self._lock:
            self._mem[fp] = dict(entry)

    # -- publishing ----------------------------------------------------------
    def publish(
        self, fp: str, mutate: Callable[[Dict[str, Any]], Optional[Dict[str, Any]]]
    ) -> bool:
        """Apply ``mutate(entry_or_empty) -> entry | None`` to plan ``fp``
        and persist. ``None`` means "nothing learned" — no write happens.
        Returns True when a publish (file or memory) occurred."""
        with self._lock:
            doc = self._read_file()
            plans = self._plans_of(doc)
            plans.update(self._mem)
            cur = plans.get(fp)
            entry = mutate(dict(cur) if isinstance(cur, dict) else {})
            if entry is None:
                return False
            entry["ts"] = time.time()
            entry["gen"] = int(entry.get("gen", 0)) + 1
            plans[fp] = entry
            self._mem[fp] = entry
            # stale-fingerprint eviction: LRU by last-used timestamp
            while len(plans) > self.max_entries:
                victim = min(
                    plans, key=lambda k: float(plans[k].get("ts", 0) or 0)
                )
                plans.pop(victim)
                self._mem.pop(victim, None)
                self._inc("evictions")
            doc.setdefault("tuning", {})
            doc["tuning"] = {"version": 1, "plans": plans}
            if self._write_doc_locked(doc):
                self._cache = plans
                self._inc("publishes")
            return True

    def _write_doc_locked(self, doc: Dict[str, Any]) -> bool:
        """Atomic whole-document write (temp in the same dir +
        ``os.replace``), refreshing the mtime cache signature. Caller
        holds ``self._lock``. False (after the one-shot unwritable
        warning) when the path can't be written — memory-only from
        there."""
        try:
            d = os.path.dirname(self.path) or "."
            os.makedirs(d, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=d, prefix="._tuned_", suffix=".json")
            try:
                with os.fdopen(fd, "w", encoding="utf-8") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            finally:
                if os.path.exists(tmp):  # replace failed
                    try:
                        os.remove(tmp)
                    except OSError:
                        pass
            try:
                st = os.stat(self.path)
                self._cache_sig = (self.path, st.st_mtime_ns, st.st_size)
            except OSError:
                self._cache_sig = (self.path, -1, -1)
            return True
        except OSError as ex:
            # unwritable store: memory-only from here on, one warning
            _warn_once(self.path, "unwritable", str(ex))
            return False
