"""Device window-function evaluation.

Lowers ``OVER (PARTITION BY ... ORDER BY ...)`` onto the device sort +
segment machinery (SURVEY §7.8): hash-repartition co-locates each
partition on one shard, ONE ``shard_map`` sorts the shard by
(validity, partition keys, order keys) and computes every window column
with prefix sums / segmented scans — no host materialization (the
reference runs OVER clauses through backend SQL on the cluster,
``fugue/execution/execution_engine.py:183-274``; pandas remains the
fallback for shapes this plan doesn't cover).

Supported here: ROW_NUMBER / RANK / DENSE_RANK / LAG / LEAD (literal
offset/default) and SUM/AVG/MIN/MAX/COUNT/FIRST/LAST over
- the whole partition (no ORDER BY, or UNBOUNDED..UNBOUNDED),
- running ROWS UNBOUNDED PRECEDING..CURRENT ROW,
- RANGE UNBOUNDED..CURRENT (peer rows share the running value),
- bounded ROWS frames for SUM/COUNT/AVG (prefix-sum differences).

NULL semantics mirror the host evaluator (``column/window.py``): NaN is
the device NULL; aggregates skip NULLs; running aggregates are NULL until
the first non-NULL; FIRST/LAST are positional. Everything else returns
None → host fallback.
"""

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..column.expressions import _LitColumnExpr, _NamedColumnExpr, _WindowExpr
from ..schema import Schema
from jax import shard_map

_AGGS = {"SUM", "AVG", "MIN", "MAX", "COUNT", "FIRST", "LAST"}
_RANKS = {"ROW_NUMBER", "RANK", "DENSE_RANK"}
_NO_LIT = object()


def _safe_mask_prefix(names: Any) -> str:
    """Sort-payload mask-column prefix that can't shadow a user column."""
    from .execution_engine import _safe_prefix

    return _safe_prefix("__wmask__", names)


def _norm_frame(expr: _WindowExpr) -> Optional[Tuple]:
    """Normalize an aggregate's frame to a hashable plan tag, or None when
    the shape needs the host evaluator."""
    has_order = len(expr.order_by) > 0
    frame = expr.frame
    if not has_order:
        return ("whole",)
    if frame is None:
        frame = ("range", "unb_prec", "current")
    kind, start, end = frame
    if start == "unb_prec" and end == "unb_foll":
        return ("whole",)
    if kind == "rows" and start == "unb_prec" and end == "current":
        return ("running",)
    if kind == "range" and start == "unb_prec" and end == "current":
        return ("peers",)
    if expr.func not in ("SUM", "COUNT", "AVG", "MIN", "MAX"):
        return None

    def off(b):
        if b == "current":
            return 0
        if isinstance(b, tuple):
            return -b[1] if b[0] == "prec" else b[1]
        return None  # unbounded

    # None offsets mean "to the segment edge" — handled statically
    if kind == "rows":
        return ("rows_bounded", off(start), off(end))
    # RANGE with value offsets: per-row frame bounds come from a binary
    # search over the (sorted) single order key — includes offset-0 bounds,
    # where value equality IS the peer group
    return ("range_bounded", off(start), off(end))


def _plan_items(
    jdf: Any, items: List[Tuple[str, _WindowExpr]]
) -> Optional[Tuple[Tuple, List[str], List[Tuple[str, bool]]]]:
    """Gate + normalize. Returns (specs, pkeys, order_items) or None."""
    if len(items) == 0:
        return None
    first = items[0][1]
    pkeys = list(first.partition_by)
    # pkeys == [] is the GLOBAL window: run_device_windows routes every row
    # to one shard (the same single-partition serialization every backend
    # pays for a global OVER) and the segment machinery sees one segment
    # one physical sort serves every spec whose ORDER BY is a PREFIX of the
    # longest one (peer detection runs per spec on its own keys)
    order_items: List[Tuple[str, bool]] = []
    for _, expr in items:
        oi = [(n, bool(a)) for n, a in expr.order_by]
        if len(oi) > len(order_items):
            if order_items != oi[: len(order_items)]:
                return None
            order_items = oi
        elif oi != order_items[: len(oi)]:
            return None
    plain = (
        lambda c: c in jdf.device_cols
        and c not in jdf.encodings
        and c not in jdf.null_masks
    )

    def groupable(c: str) -> bool:
        """Usable as a partition/order key: plain, or a SORTED dictionary
        (codes group exactly and code order == lexicographic order; -1 is
        the NULL code, flagged separately in the sort)."""
        if plain(c):
            return True
        enc = jdf.encodings.get(c)
        return (
            c in jdf.device_cols
            and c not in jdf.null_masks
            and enc is not None
            and enc.get("kind") == "dict"
            and bool(enc.get("sorted"))
        )

    def masked(c: str) -> bool:
        """A null-masked plain device column (nullable int/bool)."""
        return (
            c in jdf.device_cols
            and c in jdf.null_masks
            and c not in jdf.encodings
        )

    def orderable(c: str) -> bool:
        """Order keys additionally admit null-masked (nullable int/bool)
        columns — the mask rides the sort and flags NULL-last ordering."""
        return groupable(c) or masked(c)

    if not all(groupable(k) and not jdf.maybe_nan(k) for k in pkeys):
        return None
    if not all(orderable(n) for n, _ in order_items):
        return None
    specs: List[Tuple] = []
    for out_name, expr in items:
        if list(expr.partition_by) != pkeys:
            return None  # mixed partitions — host fallback
        func = expr.func
        n_ord = len(expr.order_by)
        if func in _RANKS:
            if func != "ROW_NUMBER" and n_ord == 0:
                return None
            specs.append((out_name, func, n_ord))
            continue
        if func in ("LAG", "LEAD"):
            if len(expr.args) < 1 or not isinstance(
                expr.args[0], _NamedColumnExpr
            ):
                return None
            arg = expr.args[0].name
            if not plain(arg):
                return None
            def lit_value(a: Any) -> Any:
                if isinstance(a, _LitColumnExpr):
                    return a.value
                # "-1.0" parses as unary negation of a literal
                from ..column.expressions import _UnaryOpExpr

                if (
                    isinstance(a, _UnaryOpExpr)
                    and a.op == "-"
                    and isinstance(a.col, _LitColumnExpr)
                    and isinstance(a.col.value, (int, float))
                ):
                    return -a.col.value
                return _NO_LIT

            offset, default = 1, None
            if len(expr.args) > 1:
                off_v = lit_value(expr.args[1])
                if off_v is _NO_LIT:
                    return None
                offset = int(off_v)
                if offset < 0:  # negative offsets flip direction — host path
                    return None
            if len(expr.args) > 2:
                default = lit_value(expr.args[2])
                if default is _NO_LIT:
                    return None
                if default is not None and not isinstance(
                    default, (int, float, bool)
                ):
                    return None
            if default is None and np.dtype(
                jdf.device_cols[arg].dtype
            ) != np.dtype(np.float64):
                # NULL fills force a float64 result — the host path keeps
                # the arg's type (incl. float32); don't let the plan change
                # output schemas
                return None
            specs.append((out_name, func, arg, offset, default))
            continue
        if func in _AGGS:
            if len(expr.args) != 1 or not isinstance(
                expr.args[0], _NamedColumnExpr
            ):
                return None
            arg = expr.args[0].name
            masked_arg = masked(arg)
            if not plain(arg) and not masked_arg:
                return None
            tag = _norm_frame(expr)
            if tag is None:
                return None
            bounded = tag[0] in ("rows_bounded", "range_bounded")
            if func in ("FIRST", "LAST") and (
                masked_arg or jdf.maybe_nan(arg)
            ):
                return None  # positional semantics vs NULL ambiguity
            if (
                not bounded
                and func not in ("COUNT", "FIRST", "LAST")
                and not masked_arg
                and np.dtype(jdf.device_cols[arg].dtype)
                != np.dtype(np.float64)
            ):
                # non-float64 SUM/MIN/MAX/AVG over running/whole/peer
                # frames: float64 accumulation would change the output type
                # (host keeps long/float) and lose int precision past 2^53
                # — host fallback. Masked args are exempt, and so are
                # bounded frames: the host evaluator itself computes those
                # in float64 and coerces back to the declared type.
                return None
            exact64 = False
            if (
                not bounded
                and masked_arg
                and func not in ("COUNT", "FIRST", "LAST")
                and np.dtype(jdf.device_cols[arg].dtype).itemsize >= 8
            ):
                # masked 64-bit ints on running/whole/peer frames: the host
                # computes these EXACTLY over extension dtypes
                # (_utils/arrow.py), so the float64 round trip (lossy past
                # 2^53) is not enough. int64 gets the exact device path
                # (hi/lo split sums, int-domain MIN/MAX — mirroring
                # ops/segment.py); uint64 falls back to the host. Bounded
                # frames stay on float64: the host itself computes those
                # in float64.
                if np.dtype(jdf.device_cols[arg].dtype) != np.dtype(
                    np.int64
                ):
                    return None
                exact64 = True
            if tag[0] == "range_bounded":
                # value-offset bounds need ONE plain numeric NaN-free
                # ORDER BY key (the host evaluator requires exactly one,
                # and NULL keys make the searched ranges ill-defined)
                if len(expr.order_by) != 1:
                    return None
                okey = expr.order_by[0][0]
                kd = (
                    np.dtype(jdf.device_cols[okey].dtype)
                    if okey in jdf.device_cols
                    else None
                )
                if (
                    not plain(okey)
                    or jdf.maybe_nan(okey)
                    or kd is None
                    or kd == np.dtype(np.bool_)
                    or not np.issubdtype(kd, np.number)
                ):
                    return None
                if not all(
                    o is None or isinstance(o, (int, float))
                    for o in tag[1:]
                ):
                    return None
            out_cast = None
            if exact64:
                specs.append((out_name, func, arg, tag, n_ord, "int64_exact"))
                continue
            if (masked_arg or bounded) and func in (
                "SUM",
                "MIN",
                "MAX",
                "AVG",
            ):
                # the host declares the ARG's type for these (int/long/
                # float/bool); the device computes float64 — mark for
                # conversion back to the EXACT declared dtype (values
                # ≤2^53 exact; the host passes through float64 too)
                import pyarrow as _pa

                tp = expr.infer_type(jdf.schema)
                if tp is not None and (
                    _pa.types.is_integer(tp)
                    or _pa.types.is_boolean(tp)
                    or tp == _pa.float32()
                ):
                    out_cast = np.dtype(tp.to_pandas_dtype()).name
            specs.append((out_name, func, arg, tag, n_ord, out_cast))
            continue
        return None
    return tuple(specs), pkeys, order_items


def plan_device_windows(
    jdf: Any, items: List[Tuple[str, _WindowExpr]]
) -> Optional[Tuple]:
    """Cheap eligibility gate — run BEFORE paying for WHERE filters or
    repartitions. Returns an opaque plan for :func:`run_device_windows`,
    or None for host fallback."""
    from .dataframe import JaxDataFrame

    if not isinstance(jdf, JaxDataFrame) or jdf.host_table is not None:
        return None
    if len(jdf.device_cols) != len(jdf.schema):
        return None
    return _plan_items(jdf, items)


def try_device_windows(
    engine: Any,
    jdf: Any,
    items: List[Tuple[str, _WindowExpr]],
) -> Optional[Any]:
    """Gate + run in one step (single-phase callers)."""
    plan = plan_device_windows(jdf, items)
    if plan is None:
        return None
    return run_device_windows(engine, jdf, plan)


def run_device_windows(engine: Any, jdf: Any, plan: Tuple) -> Optional[Any]:
    """Evaluate all window expressions on device; returns a JaxDataFrame of
    (original columns + one column per item), or None if the frame stopped
    being device-eligible since planning (e.g. a host-fallback filter)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as JP

    from ..collections.partition import PartitionSpec
    from ..parallel.mesh import ROW_AXIS
    from .dataframe import JaxDataFrame

    if not isinstance(jdf, JaxDataFrame) or jdf.host_table is not None:
        return None
    specs, pkeys, order_items = plan
    if len(pkeys) > 0:
        jdf = engine.repartition(jdf, PartitionSpec(algo="hash", by=pkeys))
    else:
        # global window: one partition ⇒ one shard (the serialization any
        # backend pays for a global OVER; other shards carry padding only)
        jdf = engine._repartition_single(jdf)
    if any(len(s) >= 6 and s[5] == "int64_exact" for s in specs):
        # the hi/lo split's float64 prefix sums are exact only while a
        # shard's low-word sum stays under 2^53: rows/shard < 2^21.
        # Checked AFTER the repartition — the exchange (hash skew, or the
        # global single-shard route) is what sets the real shard length.
        from ..parallel.mesh import num_row_shards

        padded = next(iter(jdf.device_cols.values())).shape[0]
        if padded // max(1, num_row_shards(jdf.mesh)) > (1 << 21):
            return None
    mesh = jdf.mesh
    cache = engine._jit_cache
    # null masks ride the sort as extra payload columns (mangled names) so
    # masked order keys / aggregate args keep NULL semantics
    mask_prefix = _safe_mask_prefix(jdf.schema.names)
    masked_cols = frozenset(jdf.null_masks)
    dict_cols = frozenset(
        c for c, enc in jdf.encodings.items() if enc.get("kind") == "dict"
    )
    # only ORDER-key dict membership shapes the compiled kernel (pkeys
    # compare as plain codes; payload-only encodings just ride the sort) —
    # keying on it alone keeps jit reuse across frames
    dict_order_cols = frozenset(
        n for n, _ in order_items if n in dict_cols
    )
    cache_key = (
        "window", mesh, specs, tuple(pkeys), tuple(order_items),
        dict_order_cols, masked_cols,
    )
    names_sig = tuple(jdf.schema.names)

    if (cache_key, names_sig) not in cache:

        def compute(cols: Dict[str, Any], valid: Any):
            def shard_fn(c: Dict[str, Any], v: Any):
                big = jnp.iinfo(jnp.int32).max
                ops: List[Any] = [jnp.logical_not(v)]
                for k in pkeys:
                    ops.append(c[k])
                for n, asc in order_items:
                    key = c[n]
                    if n in masked_cols:
                        # nullable int/bool: the mask flags NULL-last order
                        isnull = c[f"{mask_prefix}{n}"]
                        ops.append(isnull)
                        key = jnp.where(isnull, jnp.zeros((), key.dtype), key)
                        if not asc:
                            key = (
                                jnp.logical_not(key)
                                if key.dtype == jnp.bool_
                                else ~key
                            )
                        ops.append(key)
                    elif jnp.issubdtype(key.dtype, jnp.floating):
                        # host sorts with na_position="last"
                        isnan = jnp.isnan(key)
                        ops.append(isnan)
                        key = jnp.where(isnan, jnp.zeros((), key.dtype), key)
                        ops.append(-key if not asc else key)
                    elif n in dict_cols:
                        # sorted-dictionary codes: code order == lex order;
                        # -1 is NULL → order it LAST like the host
                        isnull = key < 0
                        ops.append(isnull)
                        ops.append(~key if not asc else key)
                    elif not asc:
                        ops.append(
                            jnp.logical_not(key)
                            if key.dtype == jnp.bool_
                            else ~key
                        )
                    else:
                        ops.append(key)
                names = list(c.keys())
                res = jax.lax.sort(
                    tuple(ops) + tuple(c[n] for n in names) + (v,),
                    num_keys=len(ops),
                )
                payload = res[len(ops):]
                sc = dict(zip(names, payload[: len(names)]))
                sv = payload[len(names)]
                n_rows = sv.shape[0]
                iota = jax.lax.iota(jnp.int32, n_rows)

                def nan_eq_diff(col: Any, mask: Any = None) -> Any:
                    a, b = col[1:], col[:-1]
                    neq = a != b
                    if jnp.issubdtype(col.dtype, jnp.floating):
                        neq = neq & ~(jnp.isnan(a) & jnp.isnan(b))
                    if mask is not None:
                        # NULLs compare equal to each other, never to values
                        ma, mb = mask[1:], mask[:-1]
                        neq = (neq & ~(ma & mb)) | (ma != mb)
                    return jnp.concatenate([jnp.ones((1,), bool), neq])

                def key_diff(n: str) -> Any:
                    m = (
                        sc[f"{mask_prefix}{n}"]
                        if n in masked_cols
                        else None
                    )
                    return nan_eq_diff(sc[n], m)

                seg_change = jnp.logical_not(sv)
                for k in pkeys:
                    seg_change = seg_change | key_diff(k)
                seg_change = seg_change.at[0].set(True)
                seg_start = jax.lax.cummax(
                    jnp.where(seg_change, iota, jnp.int32(-1))
                )

                def end_of_run(change: Any, cap_at: Any) -> Any:
                    """Last index of the run each row belongs to (a run
                    starts wherever ``change`` is True)."""
                    return jnp.minimum(
                        jnp.flip(
                            jax.lax.cummin(
                                jnp.flip(
                                    jnp.concatenate(
                                        [
                                            jnp.where(change, iota, big)[1:],
                                            jnp.full((1,), big, jnp.int32),
                                        ]
                                    )
                                )
                            )
                        )
                        - 1,
                        cap_at,
                    )

                seg_end = end_of_run(seg_change, jnp.int32(n_rows - 1))

                # peer (tied-order-key) machinery per ORDER BY prefix length
                peer_change_by: Dict[int, Any] = {0: seg_change}
                pc = seg_change
                for j, (n, _) in enumerate(order_items):
                    pc = pc | key_diff(n)
                    peer_change_by[j + 1] = pc
                peer_end_by = {
                    j: end_of_run(ch, seg_end) for j, ch in peer_change_by.items()
                }

                def seg_scan(op, x):
                    def combine(a, b):
                        af, av = a
                        bf, bv = b
                        return (af | bf, jnp.where(bf, bv, op(av, bv)))

                    _, out = jax.lax.associative_scan(
                        combine, (seg_change, x)
                    )
                    return out

                def prefix_tables(arg: Any):
                    """(masked values xm, running count n_run, running sum
                    c_run) with segment resets; NULL-skipping."""
                    x = sc[arg]
                    xf = x.astype(jnp.float64)
                    nn = sv & ~jnp.isnan(xf)
                    if arg in masked_cols:
                        nn = nn & jnp.logical_not(sc[f"{mask_prefix}{arg}"])
                    xm = jnp.where(nn, xf, 0.0)
                    c = jnp.cumsum(xm)
                    cnt = jnp.cumsum(nn.astype(jnp.float64))
                    # segment-relative prefixes via the value at seg_start
                    c0 = c[seg_start] - xm[seg_start]
                    n0 = cnt[seg_start] - nn[seg_start].astype(jnp.float64)
                    return xf, nn, xm, c - c0, cnt - n0, c, cnt

                outs: Dict[str, Any] = {}
                for spec in specs:
                    out_name, func = spec[0], spec[1]
                    if func == "ROW_NUMBER":
                        outs[out_name] = (iota - seg_start + 1).astype(jnp.int64)
                        continue
                    if func == "RANK":
                        pch = peer_change_by[spec[2]]
                        rank_start = jax.lax.cummax(
                            jnp.where(pch, iota, jnp.int32(-1))
                        )
                        outs[out_name] = (rank_start - seg_start + 1).astype(
                            jnp.int64
                        )
                        continue
                    if func == "DENSE_RANK":
                        pcum = jnp.cumsum(
                            peer_change_by[spec[2]].astype(jnp.int64)
                        )
                        outs[out_name] = pcum - pcum[seg_start] + 1
                        continue
                    if func in ("LAG", "LEAD"):
                        _, _, arg, offset, default = spec
                        x = sc[arg]
                        shift = offset if func == "LAG" else -offset
                        idx = iota - shift
                        ok = (
                            (idx >= seg_start) & (idx <= seg_end)
                            if func == "LEAD"
                            else (idx >= seg_start)
                        )
                        val = x[jnp.clip(idx, 0, n_rows - 1)]
                        if default is None:
                            valf = val.astype(jnp.float64)
                            outs[out_name] = jnp.where(ok, valf, jnp.nan)
                        else:
                            outs[out_name] = jnp.where(
                                ok, val, jnp.asarray(default, dtype=x.dtype)
                            )
                        continue
                    # aggregates
                    _, _, arg, tag, n_ord = spec[:5]
                    oc = spec[5] if len(spec) >= 6 else None
                    if oc == "int64_exact":
                        # masked int64 over running/peers/whole frames:
                        # EXACT semantics mirroring ops/segment.py — hi/lo
                        # 32-bit split sums (each side's float64 prefix sum
                        # stays exact for shards < 2^21 rows, guarded at
                        # plan-run time), recombined in wrapping int64
                        # arithmetic like the pandas oracle's cumsum;
                        # MIN/MAX scan the raw int domain.
                        x = sc[arg]
                        nnm = sv & jnp.logical_not(
                            sc[f"{mask_prefix}{arg}"]
                        )
                        nn64 = nnm.astype(jnp.float64)

                        def rel_prefix(cvals: Any) -> Any:
                            cc = jnp.cumsum(cvals)
                            return cc - (cc[seg_start] - cvals[seg_start])

                        at = (
                            iota
                            if tag[0] == "running"
                            else (
                                peer_end_by[n_ord]
                                if tag[0] == "peers"
                                else seg_end
                            )
                        )
                        count = rel_prefix(nn64)[at]
                        if func in ("SUM", "AVG"):
                            xm64 = jnp.where(nnm, x, jnp.int64(0))
                            lo32 = (
                                xm64 & jnp.int64(0xFFFFFFFF)
                            ).astype(jnp.float64)
                            hi32 = (xm64 >> 32).astype(jnp.float64)
                            s_int = (
                                rel_prefix(hi32)[at].astype(jnp.int64) << 32
                            ) + rel_prefix(lo32)[at].astype(jnp.int64)
                            if func == "SUM":
                                outs[out_name] = s_int
                                outs[f"{mask_prefix}{out_name}"] = count == 0
                            else:  # AVG: exact int sum → one f64 rounding
                                outs[out_name] = jnp.where(
                                    count > 0,
                                    s_int.astype(jnp.float64)
                                    / jnp.where(count > 0, count, 1.0),
                                    jnp.nan,
                                )
                            continue
                        # MIN/MAX in the int domain
                        op = jnp.minimum if func == "MIN" else jnp.maximum
                        fillv = (
                            jnp.iinfo(jnp.int64).max
                            if func == "MIN"
                            else jnp.iinfo(jnp.int64).min
                        )
                        xs64 = jnp.where(nnm, x, jnp.int64(fillv))
                        outs[out_name] = seg_scan(op, xs64)[at]
                        outs[f"{mask_prefix}{out_name}"] = count == 0
                        continue
                    xf, nn, xm, c_rel, n_rel, c_abs, n_abs = prefix_tables(arg)
                    if tag[0] == "whole":
                        total = c_rel[seg_end]
                        count = n_rel[seg_end]
                        if func == "COUNT":
                            outs[out_name] = count.astype(jnp.int64)
                        elif func == "SUM":
                            outs[out_name] = total
                        elif func == "AVG":
                            outs[out_name] = total / jnp.where(count > 0, count, jnp.nan)
                        elif func in ("MIN", "MAX"):
                            op = jnp.minimum if func == "MIN" else jnp.maximum
                            fill = jnp.inf if func == "MIN" else -jnp.inf
                            xs = jnp.where(nn, xf, fill)
                            run = seg_scan(op, xs)
                            ext = run[seg_end]
                            outs[out_name] = jnp.where(
                                n_rel[seg_end] > 0, ext, jnp.nan
                            )
                        elif func == "FIRST":
                            outs[out_name] = sc[arg][seg_start]
                        else:  # LAST
                            outs[out_name] = sc[arg][seg_end]
                        continue
                    if tag[0] in ("running", "peers"):
                        at = peer_end_by[n_ord] if tag[0] == "peers" else iota
                        count = n_rel[at]
                        if func == "COUNT":
                            outs[out_name] = count.astype(jnp.int64)
                        elif func in ("SUM", "AVG"):
                            s = c_rel[at]
                            r = s / count if func == "AVG" else s
                            outs[out_name] = jnp.where(count > 0, r, jnp.nan)
                        elif func in ("MIN", "MAX"):
                            op = jnp.minimum if func == "MIN" else jnp.maximum
                            fill = jnp.inf if func == "MIN" else -jnp.inf
                            xs = jnp.where(nn, xf, fill)
                            run = seg_scan(op, xs)[at]
                            outs[out_name] = jnp.where(count > 0, run, jnp.nan)
                        elif func == "FIRST":
                            outs[out_name] = sc[arg][seg_start]
                        else:  # LAST: value at the frame end
                            outs[out_name] = sc[arg][at]
                        continue
                    # bounded frames: per-row inclusive [lo, hi] indices,
                    # then prefix-diff (SUM/COUNT/AVG) or sparse-table
                    # range queries (MIN/MAX). A None offset is unbounded
                    # → the segment edge.
                    lo_off, hi_off = tag[1], tag[2]
                    if tag[0] == "rows_bounded":
                        lo = (
                            seg_start
                            if lo_off is None
                            else jnp.maximum(seg_start, iota + lo_off)
                        )
                        hi = (
                            seg_end
                            if hi_off is None
                            else jnp.minimum(seg_end, iota + hi_off)
                        )
                    else:  # range_bounded: value distances on the order key
                        okname, oasc = order_items[0]
                        kv = sc[okname].astype(jnp.float64)
                        if not oasc:
                            kv = -kv  # ascending view (host: sign * okey)

                        def bsearch(targets: Any, right: bool) -> Any:
                            """Per-row binary search of ``targets`` within
                            each row's own [seg_start, seg_end] span of the
                            sorted ``kv`` — first index where kv >= target
                            (or > target when ``right``)."""
                            def step(_, lh):
                                lo_, hi_ = lh
                                ok = lo_ < hi_
                                mid = (lo_ + hi_) // 2
                                km = kv[jnp.clip(mid, 0, n_rows - 1)]
                                go = (km <= targets) if right else (km < targets)
                                return (
                                    jnp.where(ok & go, mid + 1, lo_),
                                    jnp.where(ok & jnp.logical_not(go), mid, hi_),
                                )

                            lo0, _ = jax.lax.fori_loop(
                                0,
                                max(1, int(n_rows).bit_length()),
                                step,
                                (seg_start, seg_end + 1),
                            )
                            return lo0

                        lo = (
                            seg_start
                            if lo_off is None
                            else bsearch(kv + float(lo_off), right=False)
                        )
                        hi = (
                            seg_end
                            if hi_off is None
                            else bsearch(kv + float(hi_off), right=True) - 1
                        )
                    empty = hi < lo
                    lo_c = jnp.clip(lo, 0, n_rows - 1)
                    hi_c = jnp.clip(hi, 0, n_rows - 1)
                    count = n_abs[hi_c] - n_abs[lo_c] + nn[lo_c].astype(jnp.float64)
                    count = jnp.where(empty, 0.0, count)
                    if func == "COUNT":
                        outs[out_name] = count.astype(jnp.int64)
                    elif func in ("SUM", "AVG"):
                        s = c_abs[hi_c] - c_abs[lo_c] + xm[lo_c]
                        s = jnp.where(empty, 0.0, s)
                        if func == "SUM":
                            outs[out_name] = jnp.where(count > 0, s, jnp.nan)
                        else:
                            outs[out_name] = jnp.where(
                                count > 0,
                                s / jnp.where(count > 0, count, 1.0),
                                jnp.nan,
                            )
                    else:  # MIN/MAX: sparse table over NULL-filled values
                        op = jnp.minimum if func == "MIN" else jnp.maximum
                        fill = jnp.inf if func == "MIN" else -jnp.inf
                        xs = jnp.where(nn, xf, fill)
                        # levels cover the largest possible window length
                        if (
                            tag[0] == "rows_bounded"
                            and lo_off is not None
                            and hi_off is not None
                        ):
                            max_len = min(
                                int(n_rows), max(1, hi_off - lo_off + 1)
                            )
                        else:
                            max_len = int(n_rows)
                        lv = max(1, (max_len - 1).bit_length())
                        tables = [xs]
                        for j in range(lv):
                            stp = 1 << j
                            prev = tables[-1]
                            tables.append(
                                op(
                                    prev,
                                    jnp.concatenate(
                                        [
                                            prev[stp:],
                                            jnp.full((stp,), fill, prev.dtype),
                                        ]
                                    ),
                                )
                            )
                        st = jnp.stack(tables)  # (lv+1, n_rows)
                        ln = jnp.maximum(hi - lo + 1, 1)
                        ks = (
                            ln[:, None]
                            >= jnp.left_shift(
                                jnp.int32(1), jnp.arange(1, lv + 1, dtype=jnp.int32)
                            )[None, :]
                        ).sum(axis=1)
                        second = jnp.clip(
                            hi - jnp.left_shift(jnp.int32(1), ks) + 1,
                            0,
                            n_rows - 1,
                        )
                        res = op(st[ks, lo_c], st[ks, second])
                        outs[out_name] = jnp.where(count > 0, res, jnp.nan)
                sc_out = dict(sc)
                sc_out.update(outs)
                sc_out["__wvalid__"] = sv
                return sc_out

            return shard_map(
                shard_fn,
                mesh=mesh,
                in_specs=(JP(ROW_AXIS), JP(ROW_AXIS)),
                out_specs=JP(ROW_AXIS),
            )(cols, valid)

        cache[(cache_key, names_sig)] = jax.jit(compute)
    payload = dict(jdf.device_cols)
    for c_, m_ in jdf.null_masks.items():
        payload[f"{mask_prefix}{c_}"] = m_
    out = cache[(cache_key, names_sig)](payload, jdf.device_valid_mask())
    new_valid = out.pop("__wvalid__")
    out_masks = {
        c_: out.pop(f"{mask_prefix}{c_}") for c_ in jdf.null_masks
    }
    _PA_NAMES = {
        "int8", "int16", "int32", "int64",
        "uint8", "uint16", "uint32", "uint64",
        "float32", "float64", "bool",
    }
    import pyarrow as pa

    extra_fields = []
    for spec in specs:
        arr = out[spec[0]]
        out_cast = spec[5] if len(spec) >= 6 else None
        if out_cast == "int64_exact":
            # the kernel emitted the final dtype + a null marker directly
            if spec[1] in ("SUM", "MIN", "MAX"):
                out_masks[spec[0]] = out.pop(f"{mask_prefix}{spec[0]}")
            out_cast = None
        if out_cast is not None:
            # masked-arg/bounded-frame aggregates computed in float64 with
            # NaN=NULL — restore the exact declared dtype, like the host's
            # own float64 round trip. float32 keeps NaN as its NULL; the
            # integer/bool dtypes need a null mask.
            import jax as _jax
            import jax.numpy as _jnp

            ck = ("wcast", out_cast, mesh)
            if ck not in cache:
                if out_cast == "float32":
                    cache[ck] = _jax.jit(
                        lambda a: a.astype(_jnp.float32)
                    )
                else:

                    def _conv(a: Any, _t: str = out_cast):
                        m = _jnp.isnan(a)
                        vals = _jnp.where(m, 0.0, a).astype(_jnp.dtype(_t))
                        return vals, m

                    cache[ck] = _jax.jit(_conv)
            if out_cast == "float32":
                arr = cache[ck](arr)
                out[spec[0]] = arr
            else:
                vals, m = cache[ck](arr)
                out[spec[0]] = vals
                out_masks[spec[0]] = m
                arr = vals
        tname = str(arr.dtype)
        if tname not in _PA_NAMES:
            return None  # unexpected dtype — let the host path handle it
        extra_fields.append(pa.field(spec[0], Schema(f"x:{tname}").types[0]))
    work_schema = Schema(list(jdf.schema.fields) + extra_fields)
    return JaxDataFrame(
        mesh=mesh,
        _internal=dict(
            device_cols={n: out[n] for n in work_schema.names},
            host_tbl=None,
            row_count=jdf._row_count,
            valid_mask=new_valid,
            nan_cols=None,
            # encoded columns rode the sort as codes — their encodings
            # still describe them
            encodings=dict(jdf.encodings),
            # sorted alongside their columns — still row-aligned
            null_masks=out_masks,
            schema=work_schema,
        ),
    )
