"""Front end (``sql/fsql.py``, ``sql/local_sql.py``, ``sql/parser.py``):
the program's ``sql.parse`` spans (the FugueSQL compile and the SELECT
parse) that lie in no other ``sql.parse``, in milliseconds per completed
query."""

from benchmark.spans import outermost_ns, per_query_ms

UNIT = "ms"


def read(run):
    return per_query_ms(run, outermost_ns(run.spans, "sql.parse"))
