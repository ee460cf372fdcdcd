"""The host blocked on a device result (``ops/join.py``: the duplicate-key
check after the right side's prep and the expand count before the join's
output is sized): the program's ``device.wait`` spans, in milliseconds per
completed query. Each is a child of an engine verb, so this is time that
``verb_self_ms.power`` does not count."""

from benchmark.spans import per_query_ms

UNIT = "ms"


def read(run):
    spans = [s for s in run.spans if s["name"] == "device.wait"]
    return per_query_ms(run, sum(s["dur"] for s in spans) if spans else None)
