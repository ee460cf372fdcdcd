"""Engine verbs (``jax/execution_engine.py``): the self time of every
``engine.*`` span, its duration less the union of its direct children's
intervals, in milliseconds per completed query: the verbs' time that no
span below them names."""

from benchmark.spans import per_query_ms, self_ns

UNIT = "ms"


def read(run):
    return per_query_ms(run, self_ns(run.spans, lambda n: n.startswith("engine.")))
