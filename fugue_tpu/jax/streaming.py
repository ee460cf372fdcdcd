"""Streaming (out-of-core) device execution — SURVEY §5.7's TPU answer.

The reference never materializes a whole partition when it can stream:
Spark's pandas-UDF path iterates record batches through the executor
(`/root/reference/fugue_spark/execution_engine.py:262-294`) and chunked
map outputs flow as `LocalDataFrameIterableDataFrame`
(`/root/reference/fugue/dataframe/dataframe_iterable_dataframe.py:21`).
A `JaxDataFrame` instead puts every column fully on device, capping the
engine at HBM (~16GB on a v5e chip). This module removes that cap for
the engine verbs:

- **aggregate** — `streaming_dense_aggregate`: arrow/pandas chunks feed
  the dense-bucket groupby kernel (`ops/segment.py`) one fixed-capacity
  device batch at a time; per-bucket SUM/COUNT/MIN/MAX tables are
  DEVICE-RESIDENT accumulators merged chunk-by-chunk in one jitted step
  (donated, so XLA updates them in place). Device working set =
  O(chunk_rows × columns + buckets), independent of dataset size — the
  road to the 1B-row north star (`BASELINE.json`, NORTH_STAR.json).
- **transform** — `streaming_compiled_map`: a jax-annotated row-wise UDF
  compiled ONCE for a fixed chunk capacity, applied chunk-wise; outputs
  stream back to the host as a one-pass `LocalDataFrameIterableDataFrame`
  so neither input nor output ever fully materializes on device.
- **keyed transform / windows** — `streaming_keyed_compiled_map`: keyed
  compiled maps over KEY-CLUSTERED streams; chunks re-batch at key
  boundaries so groups stay whole, each batch runs the regular keyed
  map at one fixed capacity. With `group_ops.running_sum`/`row_number`
  this is the running-window kernel over key-partitioned streams.
- **join** — `streaming_hash_join`: stream ⋈ dimension table; sorted
  build keys replicated on device, per-chunk `searchsorted` probe,
  payloads host-side (any dtype, NULLs intact).
- **take / distinct** — running top-n / running-dedupe buffers, memory
  O(output + chunk); unsorted global take early-stops the stream.

Every path bounds device memory by `fugue.tpu.stream.chunk_rows`
(default 2^20 rows). `last_run_stats` records the measured peak live
device bytes of the most recent streaming run so tests (and users) can
PROVE the bound held.
"""

from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

from .._utils.assertion import assert_or_throw
from ..constants import (
    FUGUE_TPU_CONF_STREAM_CHUNK_ROWS,
    FUGUE_TPU_CONF_STREAM_KEY_RANGE,
)
from ..dataframe import (
    ArrowDataFrame,
    DataFrame,
    IterableDataFrame,
    LocalDataFrame,
    LocalDataFrameIterableDataFrame,
    PandasDataFrame,
)
from ..exceptions import FugueInvalidOperation
from ..schema import Schema
from jax import shard_map

DEFAULT_CHUNK_ROWS = 1 << 20

# peak live device bytes + chunk count of the most recent streaming run —
# the proof artifact that out-of-core execution really is out-of-core
last_run_stats: Dict[str, Any] = {}


def is_stream_frame(df: Any) -> bool:
    """Frames that are one-pass row streams (must NOT be materialized)."""
    return isinstance(df, (IterableDataFrame, LocalDataFrameIterableDataFrame))


def stream_parquet(
    path: Any, columns: Optional[List[str]] = None, chunk_rows: int = DEFAULT_CHUNK_ROWS
) -> LocalDataFrameIterableDataFrame:
    """Open parquet file(s) as a one-pass stream of arrow chunks — the
    out-of-core loader (datasets ≫ host/device memory never materialize).
    """
    import pyarrow.parquet as pq

    paths = [path] if isinstance(path, str) else list(path)
    first_schema = pq.ParquetFile(paths[0]).schema_arrow
    if columns is not None:
        first_schema = pa.schema([first_schema.field(c) for c in columns])

    def gen() -> Iterator[pa.Table]:
        for p in paths:
            f = pq.ParquetFile(p)
            for batch in f.iter_batches(batch_size=chunk_rows, columns=columns):
                yield pa.Table.from_batches([batch])

    return LocalDataFrameIterableDataFrame(
        (ArrowDataFrame(t) for t in gen()), schema=Schema(first_schema)
    )


# --------------------------------------------------------------------------
# chunk normalization: any stream frame -> iterator of column dicts
# --------------------------------------------------------------------------


def _iter_local_frames(df: Any, chunk_rows: int) -> Iterator[LocalDataFrame]:
    if isinstance(df, LocalDataFrameIterableDataFrame):
        yield from df.native
    elif isinstance(df, IterableDataFrame):
        # row stream -> bounded row batches
        from itertools import islice

        it = iter(df.native)
        schema = df.schema
        while True:
            rows = list(islice(it, chunk_rows))
            if len(rows) == 0:
                return
            from ..dataframe import ArrayDataFrame

            yield ArrayDataFrame(rows, schema)
    elif isinstance(df, DataFrame):
        yield df.as_local_bounded()
    else:
        raise FugueInvalidOperation(f"can't stream from {type(df)}")


def _rechunk(
    frames: Iterator[LocalDataFrame], capacity: int
) -> Iterator[LocalDataFrame]:
    """Split oversized chunks so no device batch exceeds ``capacity``
    (undersized chunks pass through; padding absorbs them)."""
    for f in frames:
        n = f.count()
        if n <= capacity:
            if n > 0:
                yield f
            continue
        if isinstance(f, ArrowDataFrame):
            tbl = f.native
            for s in range(0, n, capacity):
                yield ArrowDataFrame(tbl.slice(s, min(capacity, n - s)))
        else:
            pdf = f.as_pandas()
            for s in range(0, n, capacity):
                yield PandasDataFrame(
                    pdf.iloc[s : s + capacity], f.schema
                )


def _chunk_columns(
    f: LocalDataFrame, names: List[str]
) -> Tuple[int, Dict[str, np.ndarray], Dict[str, int]]:
    """(row_count, {name: numpy}, {name: null_count}) for one chunk.

    Float nulls surface as NaN (the device NULL); int/bool null counts are
    returned so the caller can reject them (the streaming plan has no mask
    channel — a later chunk must not silently change the type contract
    the first chunk established).
    """
    cols: Dict[str, np.ndarray] = {}
    nulls: Dict[str, int] = {}
    if isinstance(f, ArrowDataFrame):
        tbl = f.native
        n = tbl.num_rows
        for name in names:
            col = tbl.column(name)
            nulls[name] = col.null_count
            cols[name] = np.asarray(col.to_numpy(zero_copy_only=False))
    else:
        pdf = f.as_pandas()
        n = len(pdf)
        for name in names:
            s = pdf[name]
            dt = s.dtype
            if isinstance(dt, np.dtype) and dt.kind in "iubf":
                # plain numpy int/uint/bool cannot hold NULL, and float NaN
                # IS the device NULL — skip the O(n) isna scan either way
                nulls[name] = 0
            else:
                nulls[name] = int(s.isna().sum())
            cols[name] = s.to_numpy()
    return n, cols, nulls


def _device_peak_bytes() -> int:
    import jax

    return sum(
        a.nbytes for a in jax.live_arrays() if getattr(a, "is_deleted", lambda: False)() is False
    )


def _closing(chunks_it: Any) -> Iterator[Any]:
    """Consume a (possibly prefetched) chunk iterator, guaranteeing its
    producer thread is stopped on exhaustion, error, or an abandoned
    downstream generator (GeneratorExit reaches the finally)."""
    try:
        yield from chunks_it
    finally:
        chunks_it.close()


def _prefetched_pandas_chunks(
    engine: Any, df: Any, chunk_rows: int, verb: str, tune: Any = None
) -> Any:
    """The host-side chunk pipeline: decode chunks to pandas in the
    background thread while the caller consumes — used by the paths whose
    per-chunk device work happens downstream (keyed map, take, distinct,
    join probe)."""
    from .pipeline import engine_prefetcher

    frames = _maybe_coalesce(_iter_local_frames(df, chunk_rows), chunk_rows, tune)
    return engine_prefetcher(
        engine,
        (f.as_pandas() for f in frames),
        verb,
    )


def _tuned_chunk_rows(engine: Any, verb: str) -> Tuple[int, Any]:
    """Resolve one stream's chunk size: the static
    ``fugue.tpu.stream.chunk_rows`` conf, overridden by the adaptive
    tuner (``fugue_tpu/tuning``, docs/tuning.md) when an enabled run
    scope holds observations for this plan fingerprint. The returned
    handle also reaches ``engine_prefetcher`` (same verb, same run) for
    the learned prefetch depth and the telemetry feedback; outside a run
    scope — direct engine calls, ``fugue.tpu.tuning.enabled=false`` —
    this is exactly the old static resolution."""
    static = int(
        engine.conf.get(FUGUE_TPU_CONF_STREAM_CHUNK_ROWS, DEFAULT_CHUNK_ROWS)
    )
    tuner = getattr(engine, "tuner", None)
    if tuner is None:
        return static, None
    h = tuner.stream_params(verb, static)
    if h is None:
        return static, None
    return int(h.chunk_rows), h


def _maybe_coalesce(
    frames: Iterator[LocalDataFrame], target_rows: int, tune: Any
) -> Iterator[LocalDataFrame]:
    """Merge undersized source chunks up to ``target_rows`` when an
    ADAPTIVE chunk setting asks for it (``_rechunk`` only splits —
    without this, a source pre-chunked smaller than the tuned size would
    keep its per-chunk overhead no matter what the tuner learned). The
    static path never coalesces: pre-tuning chunk shapes stay
    bit-identical."""
    if tune is None or not getattr(tune, "coalesce", False) or target_rows <= 0:
        yield from frames
        return
    buf: List[LocalDataFrame] = []
    have = 0
    for f in frames:
        n = f.count()
        if n <= 0:
            continue
        if n >= target_rows and not buf:
            yield f
            continue
        buf.append(f)
        have += n
        if have >= target_rows:
            yield _concat_local(buf)
            buf, have = [], 0
    if buf:
        yield buf[0] if len(buf) == 1 else _concat_local(buf)


def _concat_local(frames: List[LocalDataFrame]) -> LocalDataFrame:
    """One frame from many (same schema — one stream's chunks)."""
    if all(isinstance(f, ArrowDataFrame) for f in frames):
        try:
            return ArrowDataFrame(pa.concat_tables([f.native for f in frames]))
        except Exception:
            pass
    import pandas as _pd

    return PandasDataFrame(
        _pd.concat([f.as_pandas() for f in frames], ignore_index=True),
        frames[0].schema,
    )


# --------------------------------------------------------------------------
# streaming dense aggregate
# --------------------------------------------------------------------------


def _fold_dense_acc(agg_sig: Tuple, acc: Tuple, outs: Tuple) -> Tuple:
    """Merge one chunk's dense-kernel output tables into the running
    device accumulators — the single fold used by the streaming aggregate
    AND the lowered-segment program (they must stay in lockstep: NaN is
    the merge identity for nullable floats, plain adds / min / max
    otherwise)."""
    import jax.numpy as jnp

    new = [acc[0] + outs[0]]  # present counts: plain int add
    for (name, agg, vi, nullable), a, b in zip(agg_sig, acc[1:], outs[1:]):
        if agg == "count":
            new.append(a + b)
        elif agg == "sum":
            if nullable:
                # NaN marks an all-NULL (or absent) bucket in a chunk
                # table — it is the merge identity
                new.append(
                    jnp.where(
                        jnp.isnan(a),
                        b,
                        jnp.where(jnp.isnan(b), a, a + b),
                    )
                )
            else:
                new.append(a + b)
        elif agg == "min":
            new.append(jnp.fmin(a, b) if nullable else jnp.minimum(a, b))
        elif agg == "max":
            new.append(jnp.fmax(a, b) if nullable else jnp.maximum(a, b))
        else:  # pragma: no cover - plan gates exclude others
            raise AssertionError(agg)
    return tuple(new)


def _identity_dense_acc(
    mesh: Any, buckets: int, agg_sig: Tuple, value_dtypes: List[np.dtype]
) -> Tuple:
    """Merge-identity accumulator tables, replicated on the mesh: folding
    a chunk's kernel output into these yields exactly that output, so the
    lowered-segment program needs ONE compiled step (no separate
    first-chunk program — one jit-cache entry per segment)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    arrs: List[np.ndarray] = [np.zeros(buckets, dtype=np.int64)]  # present
    for _, agg, vi, nullable in agg_sig:
        dt = value_dtypes[vi]
        if agg == "count":
            arrs.append(np.zeros(buckets, dtype=np.int64))
        elif agg == "sum":
            arrs.append(
                np.full(buckets, np.nan, dtype=dt)
                if nullable
                else np.zeros(buckets, dtype=dt)
            )
        elif agg == "min":
            arrs.append(
                np.full(buckets, np.nan, dtype=dt)
                if nullable
                else np.full(buckets, np.iinfo(dt).max, dtype=dt)
            )
        elif agg == "max":
            arrs.append(
                np.full(buckets, np.nan, dtype=dt)
                if nullable
                else np.full(buckets, np.iinfo(dt).min, dtype=dt)
            )
        else:  # pragma: no cover - plan gates exclude others
            raise AssertionError(agg)
    rep = NamedSharding(mesh, P())
    return tuple(jax.device_put(a, rep) for a in arrs)


def _finish_dense_host(
    engine: Any,
    acc: Tuple,
    agg_sig: Tuple,
    key: str,
    key_np: np.dtype,
    kmin: int,
    plan: dict,
    track: Optional[Callable[[], None]] = None,
) -> DataFrame:
    """ONE host transfer of the merged O(buckets) tables, then the host
    finish (avg = sum/count, declared dtypes/order) — shared by the
    streaming aggregate and the lowered-segment runner."""
    import jax

    for a in acc:
        a.copy_to_host_async()
    host = [np.asarray(jax.device_get(a)) for a in acc]
    if track is not None:
        track()
    present = host[0]
    (idx,) = np.nonzero(present > 0)
    merged: Dict[str, Any] = {key: idx.astype(np.int64) + kmin}
    for (name, _, _, _), table in zip(agg_sig, host[1:]):
        merged[name] = table[idx]
    mdf = pd.DataFrame(merged)
    out = pd.DataFrame()
    out[key] = mdf[key].astype(key_np)
    for spec in plan["post"]:
        out[spec["name"]] = spec["fn"](mdf)
    return engine.to_df(PandasDataFrame(out, plan["schema"]))


def _parse_key_range(conf: Any) -> Optional[Tuple[int, int]]:
    raw = conf.get_or_none(FUGUE_TPU_CONF_STREAM_KEY_RANGE, str)
    if raw is None or raw == "":
        return None
    try:
        lo, hi = (int(x) for x in str(raw).split(","))
    except Exception:
        raise FugueInvalidOperation(
            f"{FUGUE_TPU_CONF_STREAM_KEY_RANGE} must be 'lo,hi' ints, got {raw!r}"
        )
    assert_or_throw(lo <= hi, ValueError(f"empty key range {raw!r}"))
    return lo, hi


def streaming_dense_aggregate(
    engine: Any,
    df: Any,
    partition_spec: Any,
    agg_cols: List[Any],
) -> Optional[DataFrame]:
    """Keyed aggregate over a one-pass stream with device-resident
    accumulators. Returns None when the plan is ineligible (caller falls
    back to materializing) — eligibility mirrors the dense device
    aggregate: ONE plain int key with a bounded range, un-encoded numeric
    values, sum/count/avg/min/max only.
    """
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import ROW_AXIS, num_row_shards, pad_rows
    from ..ops.segment import (
        _DENSE_MAX_RANGE,
        _get_compiled_dense,
        dense_buckets,
    )
    from .dataframe import JaxDataFrame
    from .execution_engine import _plan_device_agg

    keys = list(partition_spec.partition_by) if partition_spec is not None else []
    if len(keys) != 1:
        return None
    mesh = engine._mesh
    shards = num_row_shards(mesh)
    chunk_rows, tune = _tuned_chunk_rows(engine, "aggregate")
    capacity = pad_rows(max(chunk_rows, shards), shards)

    # eligibility is decided from the SCHEMA alone (via an empty probe
    # frame) BEFORE any chunk is consumed — a one-pass stream must not
    # lose its head to a plan that then falls back to materialization
    empty = pa.Table.from_pylist([], schema=Schema(df.schema).pa_schema)
    jdf0 = JaxDataFrame(ArrowDataFrame(empty), mesh=mesh)
    plan = _plan_device_agg(jdf0, keys, agg_cols)
    if (
        plan is None
        or plan["virtual"]
        or plan["dict_srcs"]
        or plan["masked_srcs"]
        or any(p.get("kind") not in ("pass", "avg") for p in plan["post"])
    ):
        return None
    key = keys[0]
    key_np = np.dtype(jdf0.device_cols[key].dtype)
    if key_np.kind not in ("i", "u"):
        return None

    srcs = sorted({s for _, _, s in plan["aggs"]})
    src_np: Dict[str, np.dtype] = {}
    for s in srcs:
        dt = np.dtype(jdf0.device_cols[s].dtype)
        if dt.kind not in ("i", "u", "f"):
            return None
        src_np[s] = dt
    del jdf0

    key_range = _parse_key_range(engine.conf)
    if key_range is not None:
        kmin, kmax = key_range
        if not (0 < kmax - kmin + 1 <= _DENSE_MAX_RANGE):
            return None  # declared range too wide for the dense plan

    # ---- the stream is consumed from here on; failures now RAISE ------
    frames = _rechunk(
        _maybe_coalesce(_iter_local_frames(df, chunk_rows), chunk_rows, tune),
        capacity,
    )
    try:
        first = next(frames)
    except StopIteration:
        # empty stream: zero groups, correctly-shaped empty result
        out0 = pd.DataFrame({n: pd.Series(dtype=object) for n in plan["schema"].names})
        return engine.to_df(PandasDataFrame(out0, plan["schema"]))

    n0, cols0, nulls0 = _chunk_columns(first, [key] + srcs)
    assert_or_throw(
        nulls0[key] == 0,
        FugueInvalidOperation(f"streaming aggregate: NULL in key column {key!r}"),
    )
    probed = key_range is None
    if probed:
        key_range = (int(cols0[key].min()), int(cols0[key].max()))
    kmin, kmax = key_range
    rng = kmax - kmin + 1
    if not (0 < rng <= _DENSE_MAX_RANGE):
        raise FugueInvalidOperation(
            f"streaming aggregate: first-chunk key range [{kmin},{kmax}] "
            f"exceeds the dense plan bound ({_DENSE_MAX_RANGE}); set "
            f"{FUGUE_TPU_CONF_STREAM_KEY_RANGE} or pre-bucket the key"
        )
    buckets = dense_buckets(rng)

    # value columns dedupe by source; floats are ALWAYS NaN-aware here — a
    # later chunk may carry NaN where the first did not
    vidx = {s: i for i, s in enumerate(srcs)}
    agg_sig = tuple(
        (name, agg, vidx[src], src_np[src].kind == "f")
        for name, agg, src in plan["aggs"]
    )
    kernel = _get_compiled_dense(mesh, buckets, agg_sig)
    sharding = NamedSharding(mesh, P(ROW_AXIS))
    kmin_s = np.int64(kmin)

    # kmin is baked into the traced step as a constant — it MUST key the
    # cache or a later stream with a shifted range would reuse a stale
    # shift and scatter into wrong buckets
    cache_key = ("stream_agg_step", mesh, buckets, agg_sig, capacity, kmin)
    cache = engine._jit_cache
    if cache_key not in cache:

        def step(acc: Tuple[Any, ...], k: Any, valid: Any, *vals: Any):
            outs = kernel(k, kmin_s, *vals, valid)
            return _fold_dense_acc(agg_sig, acc, outs)

        cache[cache_key] = jax.jit(step, donate_argnums=0)
    step_fn = cache[cache_key]

    # full-capacity chunks skip the zero+copy staging buffers entirely and
    # share ONE device-resident all-valid mask (the kernel never donates
    # its chunk inputs, so the mask is reusable across every chunk)
    full_valid_dev: List[Any] = []

    def _valid_for(n: int) -> Any:
        if n == capacity:
            if not full_valid_dev:
                full_valid_dev.append(
                    jax.device_put(np.ones(capacity, dtype=bool), sharding)
                )
            return full_valid_dev[0]
        valid = np.zeros(capacity, dtype=bool)
        valid[:n] = True
        return valid

    def put_chunk(n: int, cols: Dict[str, np.ndarray], nulls: Dict[str, int]):
        assert_or_throw(
            nulls[key] == 0,
            FugueInvalidOperation(
                f"streaming aggregate: NULL in key column {key!r}"
            ),
        )
        ck = cols[key]
        lo, hi = int(ck.min()), int(ck.max())
        if lo < kmin or hi > kmax:
            hint = (
                f"probed from the first chunk as [{kmin},{kmax}]; set "
                f"{FUGUE_TPU_CONF_STREAM_KEY_RANGE}='lo,hi' to cover the "
                "full stream"
                if probed
                else f"conf {FUGUE_TPU_CONF_STREAM_KEY_RANGE} was [{kmin},{kmax}]"
            )
            raise FugueInvalidOperation(
                f"streaming aggregate: key {key!r} value outside range "
                f"([{lo},{hi}] seen): {hint}"
            )
        full = n == capacity
        if full:
            kb = np.ascontiguousarray(ck.astype(key_np, copy=False))
        else:
            kb = np.zeros(capacity, dtype=key_np)
            kb[:n] = ck
        vals = []
        for s in srcs:
            if src_np[s].kind != "f":
                assert_or_throw(
                    nulls[s] == 0,
                    FugueInvalidOperation(
                        f"streaming aggregate: NULL in non-float column "
                        f"{s!r} (first chunk established a null-free int "
                        "contract)"
                    ),
                )
            if full:
                vb = np.ascontiguousarray(
                    cols[s].astype(src_np[s], copy=False)
                )
            else:
                vb = np.zeros(capacity, dtype=src_np[s])
                vb[:n] = cols[s].astype(src_np[s], copy=False)
            vals.append(vb)
        vd = _valid_for(n)
        put = jax.device_put([kb, vd] + vals, sharding)
        return put[0], put[1], put[2:]

    stats = {"chunks": 0, "rows": 0, "peak_device_bytes": 0}

    def track() -> None:
        stats["peak_device_bytes"] = max(
            stats["peak_device_bytes"], _device_peak_bytes()
        )

    def produce() -> Iterator[Tuple[int, Any]]:
        nonlocal cols0, nulls0, first
        yield n0, put_chunk(n0, cols0, nulls0)
        cols0 = nulls0 = first = None  # release the head chunk's host copy
        for f in frames:
            n, cols, nulls = _chunk_columns(f, [key] + srcs)
            yield n, put_chunk(n, cols, nulls)

    # DOUBLE-BUFFERED ingest (ISSUE 2 tentpole): the producer thread
    # decodes + device_puts chunk i+1..i+depth while the jitted step folds
    # chunk i into the donated device accumulators
    from .pipeline import engine_prefetcher

    chunks_it = engine_prefetcher(engine, produce(), "aggregate")
    acc: Any = None
    try:
        for n, (kd, vd, ad) in chunks_it:
            if acc is None:
                acc = kernel(kd, kmin_s, *ad, vd)
            else:
                acc = step_fn(acc, kd, vd, *ad)
            stats["chunks"] += 1
            stats["rows"] += n
            del kd, vd, ad
            track()
    finally:
        chunks_it.close()

    # ONE host transfer: the merged tables (O(buckets), not O(rows))
    res = _finish_dense_host(
        engine, acc, agg_sig, key, key_np, kmin, plan, track=track
    )
    global last_run_stats
    last_run_stats = dict(stats, verb="aggregate")
    return res


# --------------------------------------------------------------------------
# lowered plan segments over one-pass streams (fugue_tpu/plan/lowering.py)
# --------------------------------------------------------------------------


def _np_dtype_of(tp: pa.DataType) -> Optional[np.dtype]:
    """Device-representable numpy dtype of an arrow type, else None."""
    try:
        if pa.types.is_boolean(tp):
            return np.dtype(bool)
        if pa.types.is_integer(tp) or pa.types.is_floating(tp):
            return np.dtype(tp.to_pandas_dtype())
    except Exception:
        return None
    return None


def _plan_lowered_chain(schema: Schema, steps: Any) -> Optional[dict]:
    """Schema-only composition of a fused step chain into its
    single-program form over RAW stream columns.

    Returns ``dict(pred, outputs, outs_by_name, need, in_np, out_np,
    schema)`` — the (possibly rewritten) Kleene-AND predicate, the output
    expressions, the input columns the program reads with their numpy
    dtypes, the EXACT device dtype of every output (zero-row eager
    probe), and the post-chain schema — or None when any step resists
    composition or device lowering. Nothing here touches data: a one-pass
    stream must not lose its head to a plan that then refuses."""
    from ..column.jax_eval import (
        can_evaluate_on_device,
        device_predicate_plan,
        evaluate_jnp,
    )
    from ..plan.fused import compose_steps
    from ..plan.ir import ALL, expr_columns

    composed = compose_steps(list(schema.names), steps)
    if composed is None:
        return None
    pred, outputs = composed
    need: set = set()
    for e in outputs:
        cols = expr_columns(e)
        if cols is ALL:
            return None
        need |= cols
    if pred is not None:
        pcols = expr_columns(pred)
        if pcols is ALL:
            return None
        need |= pcols
    in_np: Dict[str, np.dtype] = {}
    for name in sorted(need):
        if name not in schema:
            return None
        dt = _np_dtype_of(schema[name].type)
        if dt is None:
            return None
        in_np[name] = dt
    cond = None
    if pred is not None:
        p = device_predicate_plan(pred, in_np, {})
        if p is None:
            return None
        tables, cond = p
        if tables:  # pragma: no cover - raw streams carry no dict columns
            return None
    if not all(can_evaluate_on_device(e, in_np) for e in outputs):
        return None
    import jax.numpy as jnp

    zcols = {n: jnp.zeros((0,), dtype=in_np[n]) for n in sorted(need)}
    out_np: Dict[str, np.dtype] = {}
    outs_by_name: Dict[str, Any] = {}
    fields: List[pa.Field] = []
    for e in outputs:
        name = e.output_name
        if name == "" or name in outs_by_name:
            return None
        try:
            arr = jnp.asarray(evaluate_jnp(zcols, e))
        except Exception:
            return None
        out_np[name] = np.dtype(arr.dtype)
        try:
            tp = e.infer_type(schema)
        except Exception:
            tp = None
        fields.append(
            pa.field(name, tp if tp is not None else pa.from_numpy_dtype(out_np[name]))
        )
        outs_by_name[name] = e
    return dict(
        pred=cond,
        outputs=list(outputs),
        outs_by_name=outs_by_name,
        need=sorted(need),
        in_np=in_np,
        out_np=out_np,
        schema=Schema(fields),
    )


def plan_streaming_lowered_aggregate(
    engine: Any,
    df: Any,
    steps: Any,
    keys: List[str],
    agg_cols: List[Any],
    fingerprint: str,
) -> Optional[Callable[[], DataFrame]]:
    """Phase-1 (schema-only) eligibility for the flagship lowered segment:
    a fused row-local chain flowing into a dense streaming aggregate.

    Returns a zero-arg runner or None (caller falls back per-verb). The
    runner consumes the one-pass stream: the producer thread decodes and
    ``device_put``s each chunk's RAW needed columns ONCE, and a single
    jitted ``shard_map``-partitioned program — chain predicate (3-valued)
    + projections + dense-bucket kernel with in-program ``psum``/``pmin``/
    ``pmax`` cross-shard combine + accumulator fold (donated) — advances
    the device accumulators. Chunks never return to host between verbs;
    the host sees only the final O(buckets) tables. Eligibility mirrors
    the streaming dense aggregate (one plain int key, numeric un-encoded
    values, sum/count/avg/min/max) plus: every step composes and lowers
    to jnp, and the key passes through a raw input column. NOTE the key
    range and NULL contract apply to the RAW chunks — rows the fused
    filter would drop still count (the per-verb path filters first; set
    ``fugue.tpu.stream.key_range`` when that distinction matters)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..column.expressions import _NamedColumnExpr
    from ..column.jax_eval import evaluate_jnp, evaluate_jnp_3v
    from ..ops.segment import (
        _DENSE_MAX_RANGE,
        _DENSE_SUM_BACKEND,
        _get_compiled_dense,
        dense_buckets,
    )
    from ..parallel.mesh import ROW_AXIS, num_row_shards, pad_rows
    from .dataframe import JaxDataFrame
    from .execution_engine import _plan_device_agg

    if len(keys) != 1 or len(steps) == 0:
        return None
    chain = _plan_lowered_chain(Schema(df.schema), steps)
    if chain is None:
        return None
    mesh = engine._mesh
    empty = pa.Table.from_pylist([], schema=chain["schema"].pa_schema)
    try:
        jdf0 = JaxDataFrame(ArrowDataFrame(empty), mesh=mesh)
    except Exception:
        return None
    plan = _plan_device_agg(jdf0, keys, agg_cols)
    if (
        plan is None
        or plan["virtual"]
        or plan["dict_srcs"]
        or plan["masked_srcs"]
        or any(p.get("kind") not in ("pass", "avg") for p in plan["post"])
    ):
        return None
    key = keys[0]
    key_expr = chain["outs_by_name"].get(key)
    if (
        not isinstance(key_expr, _NamedColumnExpr)
        or key_expr.wildcard
        or key_expr.as_type is not None
    ):
        return None  # the group key must pass through a raw input column
    raw_key = key_expr.name
    key_np = np.dtype(jdf0.device_cols[key].dtype)
    if key_np.kind not in ("i", "u") or chain["in_np"][raw_key].kind not in ("i", "u"):
        return None
    srcs = sorted({s for _, _, s in plan["aggs"]})
    src_np: Dict[str, np.dtype] = {}
    src_expr: Dict[str, Any] = {}
    for s in srcs:
        e = chain["outs_by_name"].get(s)
        if e is None:
            return None
        dt = np.dtype(jdf0.device_cols[s].dtype)
        if dt.kind not in ("i", "u", "f"):
            return None
        src_np[s] = dt
        src_expr[s] = e
    del jdf0
    key_range = _parse_key_range(engine.conf)
    if key_range is not None and not (
        0 < key_range[1] - key_range[0] + 1 <= _DENSE_MAX_RANGE
    ):
        return None  # declared range too wide for the dense plan
    cond = chain["pred"]
    needed: List[str] = chain["need"]
    in_np: Dict[str, np.dtype] = chain["in_np"]
    shards = num_row_shards(mesh)
    label = f"segment:{fingerprint or 'anon'}"
    chunk_rows, tune = _tuned_chunk_rows(engine, label)
    capacity = pad_rows(max(chunk_rows, shards), shards)
    vidx = {s: i for i, s in enumerate(srcs)}
    # value columns dedupe by source; floats are ALWAYS NaN-aware (a later
    # chunk may carry NaN where the first did not)
    agg_sig = tuple(
        (name, agg, vidx[src], src_np[src].kind == "f")
        for name, agg, src in plan["aggs"]
    )

    def run() -> DataFrame:
        # ---- the stream is consumed from here on; failures RAISE ------
        frames = _rechunk(
            _maybe_coalesce(_iter_local_frames(df, chunk_rows), chunk_rows, tune),
            capacity,
        )
        try:
            first = next(frames)
        except StopIteration:
            out0 = pd.DataFrame(
                {n: pd.Series(dtype=object) for n in plan["schema"].names}
            )
            return engine.to_df(PandasDataFrame(out0, plan["schema"]))
        n0, cols0, nulls0 = _chunk_columns(first, needed)
        assert_or_throw(
            nulls0[raw_key] == 0,
            FugueInvalidOperation(
                f"lowered segment: NULL in key column {raw_key!r}"
            ),
        )
        probed = key_range is None
        if probed:
            kmin, kmax = int(cols0[raw_key].min()), int(cols0[raw_key].max())
        else:
            kmin, kmax = key_range
        rng = kmax - kmin + 1
        if not (0 < rng <= _DENSE_MAX_RANGE):
            raise FugueInvalidOperation(
                f"lowered segment: first-chunk RAW key range [{kmin},{kmax}] "
                f"exceeds the dense plan bound ({_DENSE_MAX_RANGE}); set "
                f"{FUGUE_TPU_CONF_STREAM_KEY_RANGE}, pre-bucket the key, or "
                "disable fugue.tpu.plan.lower_segments"
            )
        buckets = dense_buckets(rng)
        kernel = _get_compiled_dense(mesh, buckets, agg_sig)
        sharding = NamedSharding(mesh, P(ROW_AXIS))
        kmin_s = np.int64(kmin)
        cache = engine._jit_cache
        # kmin is baked into the traced step as a constant — it MUST key
        # the cache (see the streaming aggregate's identical note)
        cache_key = (
            label, mesh, buckets, agg_sig, capacity, kmin, _DENSE_SUM_BACKEND[0]
        )
        if cache_key not in cache:

            def seg_step(acc: Tuple[Any, ...], valid: Any, *arrs: Any):
                import jax.numpy as jnp

                cols = dict(zip(needed, arrs))
                v = valid
                if cond is not None:
                    pv, nl = evaluate_jnp_3v(cols, {}, {}, cond, frozenset())
                    v = v & jnp.asarray(pv, dtype=bool) & jnp.logical_not(nl)
                karr = jnp.asarray(cols[raw_key]).astype(key_np)
                vals = []
                for s in srcs:
                    a = evaluate_jnp(cols, src_expr[s])
                    if not hasattr(a, "shape") or getattr(a, "ndim", 0) == 0:
                        a = jnp.full((capacity,), a)
                    vals.append(jnp.asarray(a).astype(src_np[s]))
                outs = kernel(karr, kmin_s, *vals, v)
                return _fold_dense_acc(agg_sig, acc, outs)

            cache[cache_key] = jax.jit(seg_step, donate_argnums=0)
        step_fn = cache[cache_key]
        acc: Any = _identity_dense_acc(
            mesh, buckets, agg_sig, [src_np[s] for s in srcs]
        )
        full_valid_dev: List[Any] = []

        def _valid_for(n: int) -> Any:
            if n == capacity:
                if not full_valid_dev:
                    full_valid_dev.append(
                        jax.device_put(np.ones(capacity, dtype=bool), sharding)
                    )
                return full_valid_dev[0]
            valid = np.zeros(capacity, dtype=bool)
            valid[:n] = True
            return valid

        def put_chunk(n: int, cols: Dict[str, np.ndarray], nulls: Dict[str, int]):
            assert_or_throw(
                nulls[raw_key] == 0,
                FugueInvalidOperation(
                    f"lowered segment: NULL in key column {raw_key!r}"
                ),
            )
            ck = cols[raw_key]
            lo, hi = int(ck.min()), int(ck.max())
            if lo < kmin or hi > kmax:
                hint = (
                    f"probed from the first RAW chunk as [{kmin},{kmax}]; "
                    f"set {FUGUE_TPU_CONF_STREAM_KEY_RANGE}='lo,hi' to "
                    "cover the full stream"
                    if probed
                    else f"conf {FUGUE_TPU_CONF_STREAM_KEY_RANGE} was "
                    f"[{kmin},{kmax}]"
                )
                raise FugueInvalidOperation(
                    f"lowered segment: key {raw_key!r} value outside range "
                    f"([{lo},{hi}] seen): {hint}"
                )
            full = n == capacity
            bufs = []
            for name in needed:
                dt = in_np[name]
                if dt.kind != "f":
                    assert_or_throw(
                        nulls[name] == 0,
                        FugueInvalidOperation(
                            f"lowered segment: NULL in non-float column "
                            f"{name!r} (RAW chunks feed the device program; "
                            "rows the fused filter would drop still count)"
                        ),
                    )
                if full:
                    b = np.ascontiguousarray(cols[name].astype(dt, copy=False))
                else:
                    b = np.zeros(capacity, dtype=dt)
                    b[:n] = cols[name].astype(dt, copy=False)
                bufs.append(b)
            vd = _valid_for(n)
            put = jax.device_put([vd] + bufs, sharding)
            return put[0], tuple(put[1:])

        stats = {"chunks": 0, "rows": 0, "peak_device_bytes": 0}

        def track() -> None:
            stats["peak_device_bytes"] = max(
                stats["peak_device_bytes"], _device_peak_bytes()
            )

        def produce() -> Iterator[Tuple[int, Any]]:
            nonlocal cols0, nulls0, first
            yield n0, put_chunk(n0, cols0, nulls0)
            cols0 = nulls0 = first = None  # release the head chunk
            for f in frames:
                n, cols, nulls = _chunk_columns(f, needed)
                yield n, put_chunk(n, cols, nulls)

        # the ChunkPrefetcher feeds WHOLE segments: the producer thread
        # decodes + H2Ds raw chunks while the consumer runs the one
        # compiled program per chunk (ISSUE 7; docs/streaming.md)
        from .pipeline import engine_prefetcher

        chunks_it = engine_prefetcher(engine, produce(), label)
        try:
            for n, (vd, ad) in chunks_it:
                acc = step_fn(acc, vd, *ad)
                stats["chunks"] += 1
                stats["rows"] += n
                del vd, ad
                track()
        finally:
            chunks_it.close()
        res = _finish_dense_host(
            engine, acc, agg_sig, key, key_np, kmin, plan, track=track
        )
        global last_run_stats
        last_run_stats = dict(stats, verb=label)
        return res

    return run


def plan_lowered_steps_stream(
    engine: Any, df: Any, steps: Any, fingerprint: str
) -> Optional[Callable[[], DataFrame]]:
    """Phase-1 eligibility for a lowered chain feeding a host-buffered
    terminal (take / distinct / broadcast-join probe).

    Returns a factory producing a one-pass stream whose chunks each ran
    ONE jitted device program (raw columns H2D once; predicate +
    projections in a single dispatch; survivors compacted on host for
    the terminal's running buffer), or None. A chunk that violates the
    streaming NULL contract (NULL in a non-float column) degrades to the
    per-verb path FOR THAT CHUNK — bit-identical, never an error."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..column.jax_eval import evaluate_jnp, evaluate_jnp_3v
    from ..parallel.mesh import ROW_AXIS, num_row_shards, pad_rows

    if len(steps) == 0:
        return None
    chain = _plan_lowered_chain(Schema(df.schema), steps)
    if chain is None:
        return None
    out_schema: Schema = chain["schema"]
    if any(_np_dtype_of(f.type) is None for f in out_schema.fields):
        return None  # outputs must round-trip through numpy numerics
    mesh = engine._mesh
    shards = num_row_shards(mesh)
    chunk_rows = int(
        engine.conf.get(FUGUE_TPU_CONF_STREAM_CHUNK_ROWS, DEFAULT_CHUNK_ROWS)
    )
    capacity = pad_rows(max(chunk_rows, shards), shards)
    cond = chain["pred"]
    needed: List[str] = chain["need"]
    in_np: Dict[str, np.dtype] = chain["in_np"]
    out_np: Dict[str, np.dtype] = chain["out_np"]
    outputs = chain["outputs"]
    label = f"segment:{fingerprint or 'anon'}"
    sharding = NamedSharding(mesh, P(ROW_AXIS))

    def make_stream() -> DataFrame:
        cache = engine._jit_cache
        cache_key = (label, mesh, capacity, "chain")
        if cache_key not in cache:

            def seg_chunk(valid: Any, *arrs: Any):
                import jax.numpy as jnp

                cols = dict(zip(needed, arrs))
                v = valid
                if cond is not None:
                    pv, nl = evaluate_jnp_3v(cols, {}, {}, cond, frozenset())
                    v = v & jnp.asarray(pv, dtype=bool) & jnp.logical_not(nl)
                outs = []
                for e in outputs:
                    a = evaluate_jnp(cols, e)
                    if not hasattr(a, "shape") or getattr(a, "ndim", 0) == 0:
                        a = jnp.full((capacity,), a)
                    outs.append(
                        jnp.asarray(a).astype(out_np[e.output_name])
                    )
                return v, tuple(outs)

            cache[cache_key] = jax.jit(seg_chunk)
        fn = cache[cache_key]

        def gen() -> Iterator[LocalDataFrame]:
            for f in _rechunk(_iter_local_frames(df, chunk_rows), capacity):
                n, cols, nulls = _chunk_columns(f, needed)
                if any(
                    nulls[c] > 0 and in_np[c].kind != "f" for c in needed
                ):
                    # per-chunk graceful degradation: this chunk runs the
                    # per-verb path (bit-identical), the stream continues
                    from ..plan.fused import apply_steps_engine

                    out = apply_steps_engine(engine, f, steps)
                    if out.count() > 0:
                        yield out.as_local_bounded()
                    continue
                full = n == capacity
                bufs = []
                for name in needed:
                    dt = in_np[name]
                    if full:
                        b = np.ascontiguousarray(
                            cols[name].astype(dt, copy=False)
                        )
                    else:
                        b = np.zeros(capacity, dtype=dt)
                        b[:n] = cols[name].astype(dt, copy=False)
                    bufs.append(b)
                valid = np.zeros(capacity, dtype=bool)
                valid[:n] = True
                put = jax.device_put([valid] + bufs, sharding)
                v, outs = fn(put[0], *put[1:])
                hv = np.asarray(jax.device_get(v))
                (idx,) = np.nonzero(hv)
                if len(idx) == 0:
                    continue
                data = {}
                for fld, arr in zip(out_schema.fields, outs):
                    data[fld.name] = np.asarray(jax.device_get(arr))[idx]
                yield PandasDataFrame(pd.DataFrame(data), out_schema)

        return LocalDataFrameIterableDataFrame(gen(), schema=out_schema)

    return make_stream


def streaming_hash_join(
    engine: Any, df1: Any, df2: Any, how: str, on: Optional[List[str]] = None
) -> Optional[DataFrame]:
    """Join a one-pass stream against a materialized build side with a
    bounded device working set — the fact-stream ⋈ dimension-table shape.

    The build side (the non-stream input) is sorted by key; the sorted KEY
    column goes on device REPLICATED. Each probe chunk row-shards its key
    onto the mesh, binary-searches the build keys (``jnp.searchsorted``),
    and fetches back (hit, position); payload columns — both sides — never
    touch the device, so they keep arbitrary dtypes (strings, nullable
    ints) and NULLs. Device memory = O(build key + chunk key), independent
    of stream length — the streaming analog of the reference's per-batch
    map over a broadcast table
    (`/root/reference/fugue_spark/execution_engine.py:262-294`).
    Proof artifact: ``last_run_stats`` (verb="join").

    Eligibility (else return None → caller materializes): exactly one
    input is a stream; inner join, or the outer side IS the stream
    (left_outer with stream left, right_outer with stream right); ONE
    numeric join key; build keys unique and non-NULL (duplicate build keys
    need the expansion kernel, which has no fixed-size output per chunk).
    NULL stream keys follow SQL: never match, kept on outer joins."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..dataframe.utils import get_join_schemas, parse_join_type
    from ..parallel.mesh import ROW_AXIS, num_row_shards, pad_rows

    jt = parse_join_type(how)
    s1, s2 = is_stream_frame(df1), is_stream_frame(df2)
    if s1 == s2:
        return None
    stream_df, build_df = (df1, df2) if s1 else (df2, df1)
    if not (
        jt == "inner"
        or (jt == "left_outer" and s1)
        or (jt == "right_outer" and s2)
    ):
        return None
    key_schema, out_schema = get_join_schemas(df1, df2, how=jt, on=on)
    if len(key_schema) != 1:
        return None
    key = key_schema.names[0]
    for sch in (stream_df.schema, build_df.schema):
        f = sch[key]
        if not (pa.types.is_integer(f.type) or pa.types.is_floating(f.type)):
            return None
    outer = jt != "inner"

    if stream_df.schema[key].type != build_df.schema[key].type:
        # a dtype cast on the probe key (e.g. float->int) would truncate
        # values into false matches; value-equality across types is the
        # general path's job
        return None
    bpdf = build_df.as_local_bounded().as_pandas()
    if len(bpdf) > 0 and bpdf[key].isna().any():
        return None  # NULL build keys: let the general path handle them
    bkeys = bpdf[key].to_numpy()
    order = np.argsort(bkeys, kind="stable")
    bsorted = bkeys[order]
    if len(bsorted) > 1 and (bsorted[1:] == bsorted[:-1]).any():
        return None  # duplicates need the 1:N expansion kernel
    payload_names = [n for n in build_df.schema.names if n != key]
    n_build = len(bkeys)
    key_np = np.dtype(
        build_df.schema[key].type.to_pandas_dtype()
        if n_build > 0
        else stream_df.schema[key].type.to_pandas_dtype()
    )

    mesh = engine._mesh
    shards = num_row_shards(mesh)
    chunk_rows, tune = _tuned_chunk_rows(engine, "join")
    capacity = pad_rows(max(chunk_rows, shards), shards)

    if n_build == 0 and not outer:
        # inner ⋈ empty build = empty result; the one-pass stream need not
        # even be consumed
        empty = pd.DataFrame(
            {
                n: pd.Series(
                    dtype=np.dtype(out_schema[n].type.to_pandas_dtype())
                )
                for n in out_schema.names
            }
        )
        return engine.to_df(PandasDataFrame(empty, out_schema))

    def _extract_key(pf: pd.DataFrame):
        """(padded key buffer, null-key mask) for one chunk — NULL keys
        never match (SQL), so they probe as a harmless fill value."""
        s = pf[key]
        isna = s.isna().to_numpy()
        if isna.any():
            s = s.fillna(0)
        arr = s.to_numpy()
        if arr.dtype != key_np:
            arr = arr.astype(key_np)
        return arr, isna

    if n_build > 0:
        rep = NamedSharding(mesh, P())  # build keys: replicated on the mesh
        sharding = NamedSharding(mesh, P(ROW_AXIS))
        bk_dev = jax.device_put(bsorted.astype(key_np, copy=False), rep)
        # sorted build payload, host-side; nullable dtypes for outer joins
        # so the miss-NULLs keep their declared types (Int64/boolean/...)
        bs = bpdf.iloc[order].reset_index(drop=True)
        if outer:
            bs = pd.DataFrame(
                {n: bs[n].convert_dtypes() for n in payload_names}
            )

        cache = engine._jit_cache
        cache_key = ("stream_join", mesh, capacity, key_np.str, n_build)
        if cache_key not in cache:

            def probe(bk: Any, pk: Any, valid: Any):
                idx = jnp.searchsorted(bk, pk)
                idxc = jnp.clip(idx, 0, bk.shape[0] - 1)
                hit = (bk[idxc] == pk) & valid  # NaN keys never match (SQL)
                return hit, idxc

            cache[cache_key] = jax.jit(probe)
        probe_fn = cache[cache_key]

    def gen() -> Iterator[LocalDataFrame]:
        stats = {"chunks": 0, "rows": 0, "peak_device_bytes": 0}
        full_valid_dev: List[Any] = []
        from .pipeline import engine_prefetcher

        chunks_it = engine_prefetcher(
            engine,
            (
                f.as_pandas().reset_index(drop=True)
                for f in _rechunk(
                    _maybe_coalesce(
                        _iter_local_frames(stream_df, chunk_rows), chunk_rows, tune
                    ),
                    capacity,
                )
            ),
            "join",
        )
        for pf in _closing(chunks_it):
            n = len(pf)
            stats["chunks"] += 1
            stats["rows"] += n
            if n_build == 0:  # outer ⋈ empty build: all payloads NULL
                data = {
                    nm: (
                        pf[nm]
                        if nm in pf.columns
                        else pd.Series([pd.NA] * n).convert_dtypes()
                    )
                    for nm in out_schema.names
                }
                yield PandasDataFrame(pd.DataFrame(data), out_schema)
                continue
            karr, knull = _extract_key(pf)
            has_null = bool(knull.any())
            if n == capacity and not has_null:
                # full-capacity chunk: probe the key column directly and
                # share one device-resident all-valid mask — no staging
                kb = np.ascontiguousarray(karr)
                if not full_valid_dev:
                    full_valid_dev.append(
                        jax.device_put(np.ones(capacity, dtype=bool), sharding)
                    )
                kd, vd = jax.device_put([kb, full_valid_dev[0]], sharding)
            else:
                kb = np.zeros(capacity, dtype=key_np)
                kb[:n] = karr
                valid = np.zeros(capacity, dtype=bool)
                valid[:n] = True
                if has_null:
                    valid[:n] &= ~knull
                kd, vd = jax.device_put([kb, valid], sharding)
            hit_d, idx_d = probe_fn(bk_dev, kd, vd)
            hit_d.copy_to_host_async()
            idx_d.copy_to_host_async()
            hit = np.asarray(jax.device_get(hit_d))[:n]
            pos = np.asarray(jax.device_get(idx_d))[:n]
            stats["peak_device_bytes"] = max(
                stats["peak_device_bytes"], _device_peak_bytes()
            )
            del kd, vd, hit_d, idx_d
            data = {}
            if outer:
                hit_s = pd.Series(hit)
                for nm in out_schema.names:
                    if nm in pf.columns:
                        data[nm] = pf[nm]
                    else:
                        g = bs[nm].take(pos).reset_index(drop=True)
                        data[nm] = g.where(hit_s)
            elif hit.all():
                # every probe hit (the dimension-table norm): skip the
                # nonzero + per-column gathers — rows pass through as-is
                for nm in out_schema.names:
                    if nm in pf.columns:
                        data[nm] = pf[nm]
                    else:
                        data[nm] = bs[nm].take(pos).reset_index(drop=True)
            else:
                (sel,) = np.nonzero(hit)
                for nm in out_schema.names:
                    if nm in pf.columns:
                        data[nm] = pf[nm].take(sel).reset_index(drop=True)
                    else:
                        data[nm] = (
                            bs[nm].take(pos[sel]).reset_index(drop=True)
                        )
            yield PandasDataFrame(pd.DataFrame(data), out_schema)
        global last_run_stats
        last_run_stats = dict(stats, verb="join")

    return LocalDataFrameIterableDataFrame(gen(), schema=out_schema)


# --------------------------------------------------------------------------
# streaming compiled map
# --------------------------------------------------------------------------


def streaming_compiled_map(
    engine: Any,
    df: Any,
    fn: Callable,
    output_schema: Schema,
    on_init: Optional[Callable] = None,
) -> DataFrame:
    """Chunk-wise compiled row map over a one-pass stream.

    The jax-annotated UDF is compiled ONCE for a fixed chunk capacity
    (padding + the ``__valid__`` mask absorb short chunks) and applied per
    chunk; each output chunk is fetched to the host and yielded, so the
    result is a one-pass `LocalDataFrameIterableDataFrame` and device
    memory stays O(chunk) end to end. The streaming analog of
    `_compiled_map` (same UDF contract: dict of row-aligned arrays in,
    dict out, ``__valid__`` marks real rows).
    """
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import ROW_AXIS, num_row_shards, pad_rows

    mesh = engine._mesh
    shards = num_row_shards(mesh)
    chunk_rows, tune = _tuned_chunk_rows(engine, "map")
    capacity = pad_rows(max(chunk_rows, shards), shards)
    in_schema = df.schema
    names = list(in_schema.names)
    np_dtypes: Dict[str, np.dtype] = {}
    for f in in_schema.fields:
        if not (pa.types.is_integer(f.type) or pa.types.is_floating(f.type) or pa.types.is_boolean(f.type)):
            raise FugueInvalidOperation(
                f"streaming compiled map needs numeric/bool columns; "
                f"{f.name} is {f.type} (use a pandas-annotated transformer)"
            )
        np_dtypes[f.name] = np.dtype(f.type.to_pandas_dtype())
    sharding = NamedSharding(mesh, P(ROW_AXIS))

    cache = engine._jit_cache
    cache_key = ("stream_map", fn, mesh, capacity)
    if cache_key not in cache:
        cache[cache_key] = jax.jit(
            shard_map(fn, mesh=mesh, in_specs=(P(ROW_AXIS),), out_specs=P(ROW_AXIS))
        )
    mapped = cache[cache_key]
    if on_init is not None:
        on_init(0, df)

    out_schema = Schema(output_schema)
    out_names = list(out_schema.names)
    out_pd_dtypes = {
        f.name: np.dtype(f.type.to_pandas_dtype()) for f in out_schema.fields
    }

    def gen() -> Iterator[LocalDataFrame]:
        stats = {"chunks": 0, "rows": 0, "peak_device_bytes": 0}
        # one device-resident all-valid mask shared by every full chunk
        # (mapped() never donates inputs, so reuse is safe)
        full_valid_dev: List[Any] = []

        def produce() -> Iterator[Tuple[int, Any]]:
            for f in _rechunk(
                _maybe_coalesce(
                    _iter_local_frames(df, chunk_rows), chunk_rows, tune
                ),
                capacity,
            ):
                n, cols, nulls = _chunk_columns(f, names)
                full = n == capacity
                buf: Dict[str, Any] = {}
                for c in names:
                    if np_dtypes[c].kind != "f":
                        assert_or_throw(
                            nulls[c] == 0,
                            FugueInvalidOperation(
                                f"streaming compiled map: NULL in non-float "
                                f"column {c!r}"
                            ),
                        )
                    if full:
                        # full-capacity chunk: no staging copy at all
                        buf[c] = np.ascontiguousarray(
                            cols[c].astype(np_dtypes[c], copy=False)
                        )
                    else:
                        b = np.zeros(capacity, dtype=np_dtypes[c])
                        b[:n] = cols[c].astype(np_dtypes[c], copy=False)
                        buf[c] = b
                if full:
                    if not full_valid_dev:
                        full_valid_dev.append(
                            jax.device_put(
                                np.ones(capacity, dtype=bool), sharding
                            )
                        )
                    buf["__valid__"] = full_valid_dev[0]
                else:
                    valid = np.zeros(capacity, dtype=bool)
                    valid[:n] = True
                    buf["__valid__"] = valid
                # device_put is a no-op for the already-committed mask
                yield n, jax.device_put(buf, sharding)

        from .pipeline import engine_prefetcher

        chunks_it = engine_prefetcher(engine, produce(), "map")
        for n, dev in _closing(chunks_it):
            out = mapped(dev)
            assert_or_throw(
                isinstance(out, dict),
                FugueInvalidOperation(
                    "compiled transformer must return Dict[str, jax.Array]"
                ),
            )
            out = {k: v for k, v in out.items() if k != "__valid__"}
            missing = [c for c in out_names if c not in out]
            assert_or_throw(
                len(missing) == 0,
                FugueInvalidOperation(
                    f"compiled transformer output missing columns {missing}"
                ),
            )
            for v in out.values():
                assert_or_throw(
                    v.shape[0] == capacity,
                    FugueInvalidOperation(
                        "streaming compiled transformers must return "
                        "row-aligned arrays (padding preserved; reductions "
                        "must mask with __valid__)"
                    ),
                )
            for v in out.values():
                v.copy_to_host_async()
            host = {
                c: np.asarray(jax.device_get(out[c]))[:n] for c in out_names
            }
            stats["chunks"] += 1
            stats["rows"] += n
            stats["peak_device_bytes"] = max(
                stats["peak_device_bytes"], _device_peak_bytes()
            )
            del dev, out
            pdf = pd.DataFrame(
                {c: host[c].astype(out_pd_dtypes[c], copy=False) for c in host}
            )
            yield PandasDataFrame(pdf, out_schema)
        global last_run_stats
        last_run_stats = dict(stats, verb="map")

    return LocalDataFrameIterableDataFrame(gen(), schema=out_schema)


# --------------------------------------------------------------------------
# streaming take / distinct
# --------------------------------------------------------------------------


def streaming_take(
    engine: Any,
    df: Any,
    n: int,
    presort: Any,
    na_position: str = "last",
    partition_spec: Any = None,
) -> DataFrame:
    """``take`` over a one-pass stream with a bounded working set.

    - no presort, no keys: consume until ``n`` rows (early stop — the
      stream's tail is never generated);
    - presort: a running top-``n`` buffer merged per chunk (O(n + chunk));
    - partition keys: a running per-key head buffer (O(keys·n + chunk)).

    All row movement is host-side pandas per chunk — take outputs are
    O(n·keys), far below device-offload profitability."""
    from ..collections.partition import parse_presort_exp

    chunk_rows, tune = _tuned_chunk_rows(engine, "take")
    sorts = (
        parse_presort_exp(presort)
        if presort
        else (partition_spec.presort if partition_spec is not None else {})
    )
    keys = (
        list(partition_spec.partition_by) if partition_spec is not None else []
    )
    names = list(sorts.keys())
    asc = list(sorts.values())
    schema = Schema(df.schema)
    buf: Optional[pd.DataFrame] = None
    stats = {"chunks": 0, "rows": 0, "peak_device_bytes": 0}
    chunks_it = _prefetched_pandas_chunks(engine, df, chunk_rows, "take", tune)
    try:
        for pf in chunks_it:
            stats["chunks"] += 1
            stats["rows"] += len(pf)
            buf = pf if buf is None else pd.concat([buf, pf], ignore_index=True)
            if len(names) > 0:
                buf = buf.sort_values(
                    names, ascending=asc, na_position=na_position, kind="stable"
                )
            if len(keys) == 0:
                buf = buf.head(n)
                if len(names) == 0 and len(buf) >= n:
                    # unsorted global take: the rest of the stream is moot —
                    # close() also stops the producer's read-ahead
                    break
            else:
                buf = buf.groupby(keys, dropna=False, sort=False).head(n)
            buf = buf.reset_index(drop=True)
    finally:
        chunks_it.close()
    global last_run_stats
    last_run_stats = dict(stats, verb="take")
    out = buf if buf is not None else pd.DataFrame(columns=schema.names)
    return engine.to_df(PandasDataFrame(out, schema))


def streaming_fused_steps(engine: Any, df: Any, steps: Any) -> DataFrame:
    """Fused select/filter/assign chain applied INSIDE the chunk producer
    of a one-pass stream (plan optimizer, docs/plan.md): each chunk runs
    the chain with the engine's own verbs (device-eligible chunks take
    the same device mask/projection path the materialized frame would
    have taken — bit-identical results), and only surviving rows flow to
    the downstream jitted step. The stream stays one-pass/out-of-core:
    device working set is O(chunk), never O(dataset)."""
    from ..dataframe import ArrayDataFrame
    from ..plan.fused import apply_steps_engine

    chunk_rows = engine.conf.get(
        FUGUE_TPU_CONF_STREAM_CHUNK_ROWS, DEFAULT_CHUNK_ROWS
    )
    # schema probe on an empty frame — same inference the chunks will use
    out_schema = apply_steps_engine(
        engine, ArrayDataFrame([], df.schema), steps
    ).schema

    def gen() -> Iterator[LocalDataFrame]:
        for f in _iter_local_frames(df, chunk_rows):
            out = apply_steps_engine(engine, f, steps)
            if out.count() > 0:
                yield out.as_local_bounded()

    return LocalDataFrameIterableDataFrame(gen(), schema=out_schema)


def streaming_distinct(engine: Any, df: Any) -> DataFrame:
    """DISTINCT over a one-pass stream: chunk-wise dedupe against the
    running distinct set — memory is O(distinct rows + chunk), independent
    of stream length (SQL NaN==NaN semantics, matching the engines)."""
    chunk_rows, tune = _tuned_chunk_rows(engine, "distinct")
    from ..execution.native_execution_engine import _drop_duplicates

    schema = Schema(df.schema)
    buf: Optional[pd.DataFrame] = None
    stats = {"chunks": 0, "rows": 0, "peak_device_bytes": 0}
    chunks_it = _prefetched_pandas_chunks(engine, df, chunk_rows, "distinct", tune)
    try:
        for pf in chunks_it:
            stats["chunks"] += 1
            stats["rows"] += len(pf)
            merged = pf if buf is None else pd.concat([buf, pf], ignore_index=True)
            buf = _drop_duplicates(merged)
    finally:
        chunks_it.close()
    global last_run_stats
    last_run_stats = dict(stats, verb="distinct")
    out = buf if buf is not None else pd.DataFrame(columns=schema.names)
    return engine.to_df(PandasDataFrame(out, schema))


# --------------------------------------------------------------------------
# streaming KEYED compiled map (the out-of-core window/groupby-apply path)
# --------------------------------------------------------------------------


def streaming_keyed_compiled_map(
    engine: Any,
    df: Any,
    fn: Callable,
    output_schema: Schema,
    partition_spec: Any,
    on_init: Optional[Callable] = None,
) -> Optional[DataFrame]:
    """Keyed compiled map over a KEY-CLUSTERED one-pass stream.

    Contract: all rows of one partition key are contiguous in the stream
    (the natural layout of key-sorted files). Chunks re-batch at key
    boundaries — the trailing key's rows carry into the next batch so no
    group is ever split — then each batch runs the regular compiled keyed
    map (`JaxMapEngine._compiled_keyed_map`) on a FIXED-capacity padded
    device frame (one XLA compilation for the whole stream). With
    ``group_ops.running_sum``/``row_number`` inside the UDF this is the
    window kernel over key-partitioned streams: device memory stays
    O(capacity), independent of stream length.

    A key that reappears after its batch closed raises (the contract is
    checkable, not assumed). A single key run larger than the chunk
    capacity raises with a remediation hint. Returns None (caller
    materializes) when the schema is ineligible (non-numeric columns)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import ROW_AXIS, num_row_shards, pad_rows
    from .dataframe import JaxDataFrame

    keys = list(partition_spec.partition_by)
    if len(keys) == 0:
        return None
    in_schema = Schema(df.schema)
    np_dtypes: Dict[str, np.dtype] = {}
    for f in in_schema.fields:
        if not (
            pa.types.is_integer(f.type)
            or pa.types.is_floating(f.type)
            or pa.types.is_boolean(f.type)
        ):
            # raising (not a materializing fallback) matches the keyless
            # streaming map: a one-pass stream exists precisely because it
            # must not be materialized on device
            raise FugueInvalidOperation(
                f"streaming keyed compiled map needs numeric/bool columns; "
                f"{f.name} is {f.type} (use a pandas-annotated transformer)"
            )
        np_dtypes[f.name] = np.dtype(f.type.to_pandas_dtype())
    mesh = engine._mesh
    shards = num_row_shards(mesh)
    chunk_rows, tune = _tuned_chunk_rows(engine, "keyed_map")
    capacity = pad_rows(max(chunk_rows, shards), shards)
    sharding = NamedSharding(mesh, P(ROW_AXIS))
    out_schema = Schema(output_schema)
    map_engine = engine.map_engine
    names = list(in_schema.names)

    def run_batch(batch: pd.DataFrame, closed: set, first: List[bool]):
        uk = set(
            map(tuple, batch[keys].drop_duplicates().itertuples(index=False, name=None))
        )
        overlap = uk & closed
        assert_or_throw(
            len(overlap) == 0,
            FugueInvalidOperation(
                "streaming keyed map: the stream is not key-clustered — "
                f"key(s) {sorted(overlap)[:3]} reappeared after their rows "
                "were already processed. Sort/cluster the stream by "
                f"{keys} first."
            ),
        )
        closed |= uk
        k = len(batch)
        assert_or_throw(
            k <= capacity,
            FugueInvalidOperation(
                f"streaming keyed map: a contiguous key run ({k} rows) "
                f"exceeds the chunk capacity ({capacity}); raise "
                f"{FUGUE_TPU_CONF_STREAM_CHUNK_ROWS}"
            ),
        )
        bufs: Dict[str, Any] = {}
        for c in names:
            s = batch[c]
            assert_or_throw(
                np_dtypes[c].kind == "f" or not s.isna().any(),
                FugueInvalidOperation(
                    f"streaming keyed map: NULL in non-float column {c!r}"
                ),
            )
            b = np.zeros(capacity, dtype=np_dtypes[c])
            b[:k] = s.to_numpy().astype(np_dtypes[c], copy=False)
            bufs[c] = b
        put = jax.device_put([bufs[c] for c in names], sharding)
        jdf = JaxDataFrame(
            mesh=mesh,
            _internal=dict(
                device_cols=dict(zip(names, put)),
                host_tbl=None,
                row_count=k,  # tail-padding validity semantics
                valid_mask=None,
                schema=in_schema,
            ),
        )
        res = map_engine._compiled_keyed_map(
            jdf,
            fn,
            out_schema,
            partition_spec,
            on_init if first[0] else None,
        )
        first[0] = False
        peak = _device_peak_bytes()  # input + output batches both live here
        return res.as_pandas(), peak

    def gen() -> Iterator[LocalDataFrame]:
        stats = {"chunks": 0, "rows": 0, "peak_device_bytes": 0}
        carry: Optional[pd.DataFrame] = None
        closed: set = set()
        first = [True]
        # prefetch the host decode of the NEXT chunk while run_batch runs
        # the compiled keyed map on the current batch
        chunks_it = _prefetched_pandas_chunks(
            engine, df, chunk_rows, "keyed_map", tune
        )
        for pf in _closing(chunks_it):
            stats["chunks"] += 1
            stats["rows"] += len(pf)
            merged = (
                pf
                if carry is None or len(carry) == 0
                else pd.concat([carry, pf], ignore_index=True)
            )
            if len(merged) == 0:
                carry = None
                continue
            assert_or_throw(
                not merged[keys].isna().any().any(),
                FugueInvalidOperation(
                    "streaming keyed map: NULL/NaN partition keys are not "
                    "supported (NaN breaks key-run detection); filter or "
                    "fill the key column first"
                ),
            )
            eq_last = (
                (merged[keys] == merged[keys].iloc[-1].values)
                .all(axis=1)
                .to_numpy()
            )
            if eq_last.all():
                # one key so far: keep accumulating — but fail fast once
                # the run can no longer fit (it would only grow, with
                # quadratic host copying, before run_batch raised anyway)
                assert_or_throw(
                    len(merged) <= capacity,
                    FugueInvalidOperation(
                        f"streaming keyed map: a contiguous key run "
                        f"({len(merged)}+ rows) exceeds the chunk capacity "
                        f"({capacity}); raise {FUGUE_TPU_CONF_STREAM_CHUNK_ROWS}"
                    ),
                )
                carry = merged
                continue
            tail = int(np.argmin(eq_last[::-1]))  # trailing run length
            emit = merged.iloc[: len(merged) - tail]
            carry = merged.iloc[len(merged) - tail :].reset_index(drop=True)
            for sub in _key_aligned_splits(emit, keys, capacity):
                out, peak = run_batch(sub, closed, first)
                stats["peak_device_bytes"] = max(
                    stats["peak_device_bytes"], peak
                )
                yield PandasDataFrame(out, out_schema)
        if carry is not None and len(carry) > 0:
            for sub in _key_aligned_splits(carry, keys, capacity):
                out, peak = run_batch(sub, closed, first)
                stats["peak_device_bytes"] = max(
                    stats["peak_device_bytes"], peak
                )
                yield PandasDataFrame(out, out_schema)
        global last_run_stats
        last_run_stats = dict(stats, verb="keyed_map")

    return LocalDataFrameIterableDataFrame(gen(), schema=out_schema)


def _key_aligned_splits(
    batch: pd.DataFrame, keys: List[str], capacity: int
) -> Iterator[pd.DataFrame]:
    """Split a group-complete batch into <=capacity pieces WITHOUT cutting
    any key's run (greedy accumulation of whole groups)."""
    if len(batch) <= capacity:
        yield batch
        return
    sizes = batch.groupby(keys, dropna=False, sort=False).size().to_numpy()
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    start = 0
    cur = 0
    for gi in range(len(sizes)):
        if bounds[gi + 1] - start > capacity:
            if bounds[gi] == start:  # single group larger than capacity
                yield batch.iloc[start : bounds[gi + 1]]  # run_batch raises
                start = int(bounds[gi + 1])
                continue
            yield batch.iloc[start : bounds[gi]].reset_index(drop=True)
            start = int(bounds[gi])
        cur = int(bounds[gi + 1])
    if cur > start:
        yield batch.iloc[start:cur].reset_index(drop=True)


# --------------------------------------------------------------------------
# streaming zip/comap (key-SORTED streams, co-batched at key horizons)
# --------------------------------------------------------------------------


class ZippedStreamDataFrame(DataFrame):
    """``zip`` of key-SORTED one-pass streams (+ optionally bounded
    frames, treated as single-chunk streams).

    A thin metadata holder, like ``ZippedJaxDataFrame``: presents the blob
    protocol's logical schema so workflow metadata checks are identical,
    but physically carries the stream objects. The only consumer is
    ``comap`` (via ``streaming_comap``) — any other access raises, because
    a one-pass zipped stream cannot be materialized twice."""

    def __init__(
        self,
        streams: List[Any],
        names: List[str],
        named: bool,
        how: str,
        keys: List[str],
        schemas: List[Schema],
        presort: Dict[str, bool],
    ):
        key_schema = schemas[0].extract(keys)
        blob_fields = ",".join(
            f"__fugue_blob__{i}:binary" for i in range(len(streams))
        )
        super().__init__(Schema(str(key_schema) + "," + blob_fields))
        self.zip_streams = streams
        self.zip_names = names
        self.zip_named = named
        self.zip_how = how
        self.zip_keys = keys
        self.zip_schemas = schemas
        self.zip_presort = presort
        # the cotransform processor recognizes zipped inputs (and rebuilds
        # their empty frames) from this metadata — same contract as the
        # blob protocol and ZippedJaxDataFrame
        self.reset_metadata(
            {
                "serialized": True,
                "serialized_cols": [
                    f"__fugue_blob__{i}" for i in range(len(streams))
                ],
                "schemas": [str(s) for s in schemas],
                "serialized_has_name": named,
                "names": names,
                "how": how,
                "keys": keys,
                "stream_zip": True,
            }
        )

    @property
    def is_local(self) -> bool:
        return True

    @property
    def is_bounded(self) -> bool:
        return False  # one-pass

    @property
    def num_partitions(self) -> int:
        return 1

    @property
    def empty(self) -> bool:
        return False

    def _no(self, what: str) -> Any:
        raise FugueInvalidOperation(
            f"{what} is not available on a zipped one-pass stream; "
            "apply a cotransformer (comap) to consume it"
        )

    def peek_array(self) -> List[Any]:
        return self._no("peek")

    def count(self) -> int:
        return self._no("count")

    def as_local_bounded(self) -> Any:
        return self._no("as_local_bounded")

    def as_array(self, columns: Any = None, type_safe: bool = False) -> Any:
        return self._no("as_array")

    def as_array_iterable(self, columns: Any = None, type_safe: bool = False) -> Any:
        return self._no("as_array_iterable")

    def _drop_cols(self, cols: Any) -> Any:
        return self._no("drop")

    def _select_cols(self, cols: Any) -> Any:
        return self._no("select")

    def rename(self, columns: Any) -> Any:
        return self._no("rename")

    def alter_columns(self, columns: Any) -> Any:
        return self._no("alter_columns")

    def head(self, n: int, columns: Any = None) -> Any:
        return self._no("head")


def streaming_zip(
    engine: Any,
    dfs: Any,
    how: str,
    partition_spec: Any,
) -> Optional[DataFrame]:
    """Build a :class:`ZippedStreamDataFrame` when any zip input is a
    one-pass stream. Eligibility: a non-cross zip with explicit or
    inferable keys, and no NULL keys in the BOUNDED inputs (those need
    the blob protocol; stream inputs are checked chunk by chunk).
    Bounded inputs are host-sorted by the zip keys and ride along as
    single-chunk streams — only actual streams must arrive pre-sorted."""
    if how.lower() == "cross":
        return None
    keys = list(partition_spec.partition_by) if partition_spec is not None else []
    if len(keys) == 0 and len(dfs) > 0:
        keys = [
            n
            for n in dfs[0].schema.names
            if all(n in d.schema for d in dfs.values())
        ]
    if len(keys) == 0:
        return None
    schemas = [Schema(d.schema) for d in dfs.values()]
    inputs: List[Any] = []
    for d in dfs.values():
        if is_stream_frame(d):
            inputs.append(d)
            continue
        pf = d.as_pandas()
        if len(pf) > 0 and pf[keys].isna().any().any():
            # NULL keys need the blob protocol's NULL-group handling
            return None
        inputs.append(
            PandasDataFrame(
                pf.sort_values(keys, kind="stable").reset_index(drop=True),
                Schema(d.schema),
            )
        )
    presort = dict(partition_spec.presort) if partition_spec is not None else {}
    return ZippedStreamDataFrame(
        streams=inputs,
        names=list(dfs.keys()),
        named=dfs.has_key,
        how=how.lower(),
        keys=keys,
        schemas=schemas,
        presort=presort,
    )


def _key_view(frame: pd.DataFrame, keys: List[str]) -> Any:
    """A lexicographically comparable view of the key columns: the bare
    numpy column for one key (fast path), a MultiIndex otherwise."""
    if len(keys) == 1:
        return frame[keys[0]].to_numpy()
    return pd.MultiIndex.from_frame(frame[keys])


def _is_sorted(kv: Any) -> bool:
    if isinstance(kv, pd.MultiIndex):
        return kv.is_monotonic_increasing
    return bool(np.all(kv[1:] >= kv[:-1])) if len(kv) > 1 else True


def _split_below(b: pd.DataFrame, keys: List[str], horizon: Tuple) -> int:
    """Index of the first row with key >= horizon (buffer is sorted)."""
    kv = _key_view(b, keys)
    if isinstance(kv, pd.MultiIndex):
        # lexicographic binary search over the sorted MultiIndex
        lo, hi = 0, len(kv)
        while lo < hi:
            mid = (lo + hi) // 2
            if tuple(kv[mid]) < horizon:
                lo = mid + 1
            else:
                hi = mid
        return lo
    return int(np.searchsorted(kv, horizon[0], side="left"))


def streaming_comap(
    engine: Any,
    zdf: "ZippedStreamDataFrame",
    map_func: Callable,
    output_schema: Any,
    partition_spec: Any = None,
    on_init: Optional[Callable] = None,
) -> DataFrame:
    """Cotransform over zipped key-SORTED streams with bounded memory.

    The classic sorted-merge co-batching: each input keeps a buffer; the
    emit horizon is the smallest "last key seen" over non-exhausted
    inputs; rows strictly below the horizon are complete on every input
    (ascending-sorted contract, validated chunk by chunk) and batch
    through the regular zip+comap; rows at/above it carry. Memory is
    O(chunk × inputs), independent of stream length."""
    from ..dataframe import DataFrames

    out_schema = (
        output_schema if isinstance(output_schema, Schema) else Schema(output_schema)
    )
    keys = zdf.zip_keys
    chunk_rows = int(
        engine.conf.get(FUGUE_TPU_CONF_STREAM_CHUNK_ROWS, DEFAULT_CHUNK_ROWS)
    )
    from ..collections.partition import PartitionSpec as _PSpec

    # presort precedence matches the non-streaming comap: a comap-time
    # presort overrides the zip-time one
    presort = dict(zdf.zip_presort)
    if partition_spec is not None and len(partition_spec.presort) > 0:
        presort = dict(partition_spec.presort)
    spec = (
        _PSpec(partition_spec, by=keys, presort=presort)
        if partition_spec is not None
        else _PSpec(by=keys, presort=presort)
    )

    def gen() -> Iterator[LocalDataFrame]:
        stats = {"chunks": 0, "rows": 0, "peak_device_bytes": 0}
        iters = [
            _iter_local_frames(s, chunk_rows) for s in zdf.zip_streams
        ]
        # chunk LISTS, concatenated only at emit time: per-pull concat
        # would be O(run^2) copying while a hot key spans many chunks
        bufs: List[List[pd.DataFrame]] = [[] for _ in iters]
        last_key: List[Optional[Tuple]] = [None] * len(iters)
        done = [False] * len(iters)
        first = [True]

        def _nrows(i: int) -> int:
            return sum(len(c) for c in bufs[i])

        def pull(i: int) -> bool:
            """Append ONE validated chunk to input i's buffer; False at
            stream end. The one place every chunk enters a buffer — the
            sorted-contract checks live here and only here."""
            try:
                f = next(iters[i])
            except StopIteration:
                done[i] = True
                return False
            pf = f.as_pandas().reset_index(drop=True)
            stats["chunks"] += 1
            stats["rows"] += len(pf)
            if len(pf) == 0:
                return True
            kv = pf[keys]
            assert_or_throw(
                not kv.isna().any().any(),
                FugueInvalidOperation(
                    "streaming zip: NULL keys are not supported on the "
                    "sorted-stream path"
                ),
            )
            assert_or_throw(
                _is_sorted(_key_view(pf, keys)),
                FugueInvalidOperation(
                    f"streaming zip: input {i} is not sorted ascending "
                    f"by {keys} within a chunk"
                ),
            )
            lo = tuple(pf[keys].iloc[0])
            if last_key[i] is not None:
                assert_or_throw(
                    lo >= last_key[i],
                    FugueInvalidOperation(
                        f"streaming zip: input {i} is not sorted "
                        f"ascending by {keys} ({lo!r} after {last_key[i]!r})"
                    ),
                )
            bufs[i].append(pf)
            last_key[i] = tuple(pf[keys].iloc[-1])
            return True

        def run_batch(parts: List[pd.DataFrame]):
            pieces = DataFrames(
                dict(zip(zdf.zip_names, (
                    PandasDataFrame(p, s)
                    for p, s in zip(parts, zdf.zip_schemas)
                )))
                if zdf.zip_named
                else [
                    PandasDataFrame(p, s)
                    for p, s in zip(parts, zdf.zip_schemas)
                ]
            )
            z = engine.zip(pieces, how=zdf.zip_how, partition_spec=spec)
            res = engine.comap(
                z,
                map_func,
                out_schema,
                partition_spec=spec,
                on_init=on_init if first[0] else None,
            )
            first[0] = False
            out = res.as_pandas()
            stats["peak_device_bytes"] = max(
                stats["peak_device_bytes"], _device_peak_bytes()
            )
            return out

        while True:
            for i in range(len(iters)):
                while not done[i] and _nrows(i) == 0:
                    pull(i)
            live = [i for i in range(len(iters)) if _nrows(i) > 0]
            if len(live) == 0:
                break
            # horizon: the smallest last-key over inputs that may still grow
            horizons = [last_key[i] for i in live if not done[i]]
            horizon = min(horizons) if len(horizons) > 0 else None
            parts: List[pd.DataFrame] = []
            any_rows = False
            for i in range(len(iters)):
                if _nrows(i) == 0:
                    parts.append(pd.DataFrame(columns=zdf.zip_schemas[i].names))
                    continue
                if horizon is not None and tuple(
                    bufs[i][0][keys].iloc[0]
                ) >= horizon:
                    # whole buffer at/above the horizon: nothing to emit —
                    # skip the concat (a stalled input must not be
                    # re-copied every round)
                    parts.append(pd.DataFrame(columns=zdf.zip_schemas[i].names))
                    continue
                b = (
                    bufs[i][0]
                    if len(bufs[i]) == 1
                    else pd.concat(bufs[i], ignore_index=True)
                )
                cut = len(b) if horizon is None else _split_below(b, keys, horizon)
                parts.append(b.iloc[:cut].reset_index(drop=True))
                rest = b.iloc[cut:].reset_index(drop=True)
                bufs[i] = [rest] if len(rest) > 0 else []
                any_rows = any_rows or cut > 0
            if any_rows:
                yield PandasDataFrame(run_batch(parts), out_schema)
            elif horizon is not None:
                # nothing below the horizon: only the inputs PINNED at the
                # horizon can extend it — drain one chunk from each (ahead
                # inputs must not grow, or the memory bound erodes)
                progressed = False
                for i in range(len(iters)):
                    if (
                        not done[i]
                        and _nrows(i) > 0
                        and last_key[i] == horizon
                    ):
                        pull(i)
                        progressed = True
                assert_or_throw(
                    progressed,
                    FugueInvalidOperation(
                        "streaming zip: no progress possible (internal)"
                    ),
                )
        if first[0] and on_init is not None:
            # zero non-empty batches: on_init still fires once over empty
            # frames (non-streaming comap parity)
            on_init(
                0,
                DataFrames(
                    dict(zip(zdf.zip_names, (
                        PandasDataFrame(
                            pd.DataFrame(columns=s.names), s
                        )
                        for s in zdf.zip_schemas
                    )))
                    if zdf.zip_named
                    else [
                        PandasDataFrame(pd.DataFrame(columns=s.names), s)
                        for s in zdf.zip_schemas
                    ]
                ),
            )
        global last_run_stats
        last_run_stats = dict(stats, verb="comap")

    return LocalDataFrameIterableDataFrame(gen(), schema=out_schema)
