"""Serve layer (``serve/server.py``): the mean of the program's
``serve.deliver`` spans, each from the end of an execution's run to its
waiters woken (the result's rows and bytes, the tenants' accounting, the
wake-up and the retention ring), in milliseconds. None where the program
opens no such span."""

UNIT = "ms"


def read(run):
    d = [s["dur"] for s in run.spans if s["name"] == "serve.deliver"]
    return sum(d) / len(d) / 1e6 if d else None
