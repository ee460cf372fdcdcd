"""File read (``jax/execution_engine.load_df``): the program's ``io.read``
spans around the host engine's file read, in milliseconds per completed
query."""

from benchmark.spans import per_query_ms

UNIT = "ms"


def read(run):
    spans = [s for s in run.spans if s["name"] == "io.read"]
    return per_query_ms(run, sum(s["dur"] for s in spans) if spans else None)
