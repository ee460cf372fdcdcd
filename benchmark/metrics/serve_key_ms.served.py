"""Serve layer (``serve/server.py``, ``serve/dedup.py``): the program's
``serve.key`` spans, each the dry optimize and fingerprint that decide a
submission's in-flight dedup key inside ``serve.submit``, summed over the
window and given in milliseconds per completed query. None where the
program opens no such span."""

from benchmark.spans import per_query_ms

UNIT = "ms"


def read(run):
    spans = [s for s in run.spans if s["name"] == "serve.key"]
    return per_query_ms(run, sum(s["dur"] for s in spans) if spans else None)
