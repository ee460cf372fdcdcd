"""Generate the per-module API reference (docs/api/*.md) from docstrings.

Run from the repo root:  python docs/gen_api.py
The output is committed so the reference is readable without running
anything; re-run after changing public APIs.
"""

import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "api")

# the public surface, module by module (mirrors the reference's per-module
# rst tree under /root/reference/docs/api*)
MODULES = [
    ("fugue_tpu.api", "Top-level functional API (`fa.*`)"),
    ("fugue_tpu.schema", "Schema"),
    ("fugue_tpu.dataframe", "DataFrames (local frames, conversion utils)"),
    ("fugue_tpu.dataset", "Dataset base"),
    ("fugue_tpu.bag", "Bags"),
    ("fugue_tpu.collections", "PartitionSpec / raw SQL / yields"),
    ("fugue_tpu.column", "Column expressions"),
    ("fugue_tpu.execution", "Engine contract + native engine + factory"),
    ("fugue_tpu.extensions", "Creator/Processor/Outputter/(Co)Transformer"),
    ("fugue_tpu.workflow", "Workflow DAG, checkpoints, modules"),
    ("fugue_tpu.sql", "FugueSQL, parser, executor, dialect transpiler"),
    ("fugue_tpu.jax", "The TPU execution engine (device frames, group_ops, streaming)"),
    ("fugue_tpu.jax.group_ops", "Per-group reductions for compiled keyed transformers"),
    ("fugue_tpu.jax.streaming", "Out-of-core streaming execution"),
    ("fugue_tpu.warehouse", "DB-API warehouse engine + driver profiles"),
    ("fugue_tpu.warehouse.profile", "Warehouse driver profiles"),
    ("fugue_tpu.ops", "Device kernels (segment/shuffle/join/collectives)"),
    ("fugue_tpu.parallel", "Mesh, distributed init, profiler"),
    ("fugue_tpu.rpc", "Worker-to-driver callbacks"),
    ("fugue_tpu.serve", "Multi-tenant engine server (admission, dedup, budgets)"),
    ("fugue_tpu.views", "Continuous views (registry, watch leases, maintainer)"),
    ("fugue_tpu.dist", "Multi-host worker tier (leases, heartbeats, supervisor)"),
    ("fugue_tpu.obs", "Observability (tracer, cluster traces, flight recorder, metrics)"),
    ("fugue_tpu.tuning", "Adaptive tuning (learned settings)"),
    ("fugue_tpu.analysis", "UDF static analyzer (AST trace, translation, lint)"),
    ("fugue_tpu.test", "Test harness plugins (fugue_test_suite/with_backend)"),
    ("fugue_tpu.notebook", "Notebook %%fsql magic"),
    ("fugue_tpu.constants", "Configuration keys"),
]


def _doc_first(obj, n=None) -> str:
    """The docstring's whole first paragraph (up to the first blank line) —
    truncating at a fixed line count published half-sentences."""
    doc = inspect.getdoc(obj) or ""
    head = []
    for ln in doc.splitlines():
        if ln.strip() == "" and head:
            break
        head.append(ln)
    return " ".join(s.strip() for s in head).strip()


def _sig(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _public_members(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in dir(mod) if not n.startswith("_")]
    out = []
    for n in sorted(set(names)):
        try:
            obj = getattr(mod, n)
        except AttributeError:
            continue
        if inspect.ismodule(obj):
            continue
        home = getattr(obj, "__module__", "") or ""
        if not home.startswith("fugue_tpu"):
            continue
        out.append((n, obj))
    return out


def render(mod_name: str, title: str) -> str:
    mod = importlib.import_module(mod_name)
    lines = [f"# `{mod_name}`", "", title, ""]
    mdoc = _doc_first(mod, n=4)
    if mdoc:
        lines += [mdoc, ""]
    classes = [(n, o) for n, o in _public_members(mod) if inspect.isclass(o)]
    funcs = [
        (n, o)
        for n, o in _public_members(mod)
        if inspect.isfunction(o) or inspect.isbuiltin(o)
    ]
    consts = [
        (n, o)
        for n, o in _public_members(mod)
        if not inspect.isclass(o)
        and not callable(o)
        and isinstance(o, (str, int, float, tuple, frozenset))
    ]
    if classes:
        lines.append("## Classes\n")
        for n, c in classes:
            lines.append(f"### `{n}`\n")
            d = _doc_first(c)
            if d:
                lines.append(d + "\n")
            methods = [
                (mn, m)
                for mn, m in inspect.getmembers(c, predicate=inspect.isfunction)
                if not mn.startswith("_") and mn in c.__dict__
            ]
            props = [
                (mn, m)
                for mn, m in inspect.getmembers(
                    c, predicate=lambda x: isinstance(x, property)
                )
                if not mn.startswith("_") and mn in c.__dict__
            ]
            for mn, m in props:
                pd = _doc_first(m.fget) if m.fget else ""
                lines.append(f"- `{mn}` *(property)* — {pd}")
            for mn, m in methods:
                lines.append(f"- `{mn}{_sig(m)}` — {_doc_first(m, 2)}")
            if methods or props:
                lines.append("")
    if funcs:
        lines.append("## Functions\n")
        for n, f in funcs:
            lines.append(f"### `{n}{_sig(f)}`\n")
            d = _doc_first(f)
            if d:
                lines.append(d + "\n")
    if consts:
        lines.append("## Constants\n")
        for n, v in consts:
            lines.append(f"- `{n} = {v!r}`")
        lines.append("")
    return "\n".join(lines) + "\n"


def main() -> None:
    os.makedirs(OUT, exist_ok=True)
    index = [
        "# fugue_tpu API reference",
        "",
        "Generated from docstrings by `docs/gen_api.py` — regenerate after",
        "changing public APIs.",
        "",
    ]
    for mod_name, title in MODULES:
        fn = mod_name.replace(".", "_") + ".md"
        try:
            content = render(mod_name, title)
        except Exception as e:  # pragma: no cover
            print(f"SKIP {mod_name}: {e}", file=sys.stderr)
            continue
        with open(os.path.join(OUT, fn), "w") as f:
            f.write(content)
        index.append(f"- [`{mod_name}`]({fn}) — {title}")
        print("wrote", fn)
    with open(os.path.join(OUT, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")


if __name__ == "__main__":
    main()
