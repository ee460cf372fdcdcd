"""Device to host (``jax/dataframe.py`` ``as_arrow``): the program's
``frame.fetch`` spans, which read device columns back and decode them, in
milliseconds per completed query. Where a frame was ingested in the same
query, the read back first waits for that ingest's transfers to land, so
the fetch then holds the tail of the host-to-device copy too."""

from benchmark.spans import per_query_ms

UNIT = "ms"


def read(run):
    spans = [s for s in run.spans if s["name"] == "frame.fetch"]
    return per_query_ms(run, sum(s["dur"] for s in spans) if spans else None)
