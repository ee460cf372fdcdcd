"""Host encode and transfer issue (``jax/dataframe.py`` ``_from_arrow``):
the program's ``frame.ingest`` spans, each the encoding of one frame and the
issue of its transfers to the device, in milliseconds per completed query.
The span does not wait for the transfers: what is still in flight when it
closes completes inside a later ``frame.fetch``."""

from benchmark.spans import per_query_ms

UNIT = "ms"


def read(run):
    spans = [s for s in run.spans if s["name"] == "frame.ingest"]
    return per_query_ms(run, sum(s["dur"] for s in spans) if spans else None)
