"""Host operators (the pandas fallbacks of the engine's verbs and the SQL
ORDER BY): the self time of the program's ``host.op`` spans (less the
fetches and ingests inside them), in milliseconds per completed query."""

from benchmark.spans import per_query_ms, self_ns

UNIT = "ms"


def read(run):
    return per_query_ms(run, self_ns(run.spans, lambda n: n == "host.op"))
