"""Serve layer (``serve/server.py``): the share of the traced window in
which two or more of the program's ``serve.run`` spans were open at once
(two executions on one engine at the same time), in percent of the
window's host-clock length. None without a ``serve.run`` span."""

UNIT = "%"


def read(run):
    edges = []
    for s in run.spans:
        if s["name"] == "serve.run":
            edges += [(s["ts"], 1), (s["ts"] + s["dur"], -1)]
    if not edges or run.window_s <= 0:
        return None
    edges.sort()  # at a tie the closing edge (-1) comes first
    both, open_, since = 0, 0, 0
    for t, step in edges:
        if open_ >= 2:
            both += t - since
        open_ += step
        since = t
    return 100.0 * both / 1e9 / run.window_s
