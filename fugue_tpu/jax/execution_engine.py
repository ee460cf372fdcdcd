"""JaxExecutionEngine — the TPU-native distributed engine (the north star).

Design (SURVEY §7.8, BASELINE.json north_star):

- ``to_df``: arrow → :class:`JaxDataFrame` (row-sharded device arrays over a
  ``Mesh``) via ``jax.device_put`` with ``NamedSharding(mesh, P("rows"))``.
- ``JaxMapEngine.map_dataframe``:
  * **compiled path** — transformers whose params are annotated
    ``Dict[str, jax.Array]`` (format hint "jax") and need no key grouping
    run as ONE ``shard_map`` compiled by XLA across the mesh: the user fn
    traces per shard; no Python per row, no host round trip;
  * **general path** — any Python function: host-side sort+groupby apply
    (the correctness path, same semantics as the native engine), output
    re-sharded to device. This mirrors the Spark engine's pandas-UDF vs RDD
    path split (reference ``fugue_spark/execution_engine.py:137``).
- ``aggregate``: two-phase device groupby (``ops/segment.py``): O(rows)
  lexicographic sort + segment reduction per shard on device, O(groups)
  merge on host.
- ``select``/``assign``/``filter``: column-IR compiled with jax.numpy when
  every referenced column is device-resident; host fallback otherwise.
- ``broadcast``: replicated sharding; ``persist``: device-resident pinning
  (block_until_ready); relational ops without a device kernel yet fall back
  to the in-process oracle engine — the same escape-hatch layering the
  reference uses (Ray extends DuckDB, ``fugue_ray/execution_engine.py:204``).
"""

import logging
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np
import pandas as pd
import pyarrow as pa

from .._utils.assertion import assert_or_throw
from ..collections.partition import PartitionCursor, PartitionSpec
from ..column import ColumnExpr, SelectColumns
from ..column.jax_eval import can_evaluate_on_device, evaluate_jnp, pa_type_to_np_dtype
from ..dataframe import (
    ArrowDataFrame,
    DataFrame,
    DataFrames,
    LocalDataFrame,
    PandasDataFrame,
)
from ..exceptions import FugueInvalidOperation
from ..execution.execution_engine import ExecutionEngine, MapEngine, SQLEngine
from ..execution.native_execution_engine import NativeExecutionEngine, PandasMapEngine
from ..parallel.mesh import (
    ROW_AXIS,
    build_mesh,
    num_row_shards,
    replicated_sharding,
    row_sharding,
)
from ..schema import Schema
from .dataframe import JaxDataFrame, _DEVICE_DTYPES
from ..obs import NULL_SPAN, get_host_op_stats, get_tracer, traced_verb
from jax import shard_map


def _safe_prefix(base: str, *name_sets: Any) -> str:
    """Internal payload-column prefix guaranteed not to shadow a user column
    (a user column may literally be named ``__mask__x``): prepend ``_`` until
    no provided name starts with the prefix."""
    p = base
    while any(any(str(n).startswith(p) for n in ns) for ns in name_sets):
        p = "_" + p
    return p


class JaxMapEngine(MapEngine):
    @property
    def is_distributed(self) -> bool:
        return True

    @property
    def map_handles_repartition(self) -> bool:
        """Both map paths group internally (host: sort+groupby; compiled:
        per-shard trace) — a device all-to-all before the map would be paid
        and then ignored."""
        return True

    @property
    def execution_engine_constraint(self) -> type:
        return JaxExecutionEngine

    @traced_verb("engine.transform")
    def map_dataframe(
        self,
        df: DataFrame,
        map_func: Callable[[PartitionCursor, LocalDataFrame], LocalDataFrame],
        output_schema: Any,
        partition_spec: PartitionSpec,
        on_init: Optional[Callable[[int, DataFrame], Any]] = None,
        map_func_format_hint: Optional[str] = None,
    ) -> DataFrame:
        engine: JaxExecutionEngine = self.execution_engine  # type: ignore
        output_schema = (
            output_schema if isinstance(output_schema, Schema) else Schema(output_schema)
        )
        if map_func_format_hint == "jax":
            raw = _sniff_jax_func(map_func)
            if raw is not None and len(partition_spec.partition_by) == 0:
                from .streaming import is_stream_frame, streaming_compiled_map

                if is_stream_frame(df):
                    # one-pass stream + keyless compiled UDF: chunk-wise
                    # out-of-core map — never materializes on device
                    return streaming_compiled_map(
                        engine, df, raw, output_schema, on_init
                    )
            elif raw is not None:
                from .streaming import (
                    is_stream_frame,
                    streaming_keyed_compiled_map,
                )

                if is_stream_frame(df):
                    # key-clustered stream + keyed compiled UDF: re-batch
                    # at key boundaries, fixed-capacity device batches
                    # (raises with remediation when ineligible — a one-pass
                    # stream must never silently materialize on device)
                    return streaming_keyed_compiled_map(
                        engine, df, raw, output_schema, partition_spec, on_init
                    )
            if raw is not None:
                jdf = engine.to_df(df)
                keys = list(partition_spec.partition_by)
                # encoded/masked columns have non-plain semantics the UDF
                # can't see — host path renders them as real values. The
                # ONE exception: dictionary-encoded PARTITION keys, whose
                # codes the UDF only groups by and passes through opaquely
                # (the engine reattaches the dictionary on output).
                if isinstance(jdf, JaxDataFrame) and len(keys) == 0:
                    if not jdf.has_encoded:
                        # the compiled path maps shards IN PLACE — an even/
                        # rand spec still needs its physical exchange first
                        # (the processor no longer repartitions for this
                        # engine)
                        if not partition_spec.empty:
                            jdf = engine.repartition(jdf, partition_spec)  # type: ignore[assignment]
                        return self._compiled_map(jdf, raw, output_schema, on_init)
                elif isinstance(jdf, JaxDataFrame):
                    dict_keys_only = len(jdf.null_masks) == 0 and all(
                        e.get("kind") == "dict" and c in keys
                        for c, e in jdf.encodings.items()
                    )
                    # an encoded key that appears in the output must keep
                    # its declared type — the dictionary is reattached to
                    # the (passed-through) codes
                    enc_schema_ok = all(
                        k not in output_schema
                        or output_schema[k].type == jdf.schema[k].type
                        for k in jdf.encodings
                    )
                    nan_key = any(
                        np.issubdtype(
                            np.dtype(jdf.device_cols[k].dtype), np.floating
                        )
                        and jdf.maybe_nan(k)
                        for k in keys
                        if k in jdf.device_cols
                    )
                    if (
                        all(k in jdf.device_cols for k in keys)
                        and not nan_key
                        and jdf.host_table is None
                        and (
                            not jdf.has_encoded
                            or (dict_keys_only and enc_schema_ok)
                        )
                    ):
                        return self._compiled_keyed_map(
                            jdf, raw, output_schema, partition_spec, on_init
                        )
                if len(keys) > 0:
                    # keyed jax UDFs depend on the reserved __segments__/
                    # __valid__ contract that only the compiled plans
                    # provide — a silent host fallback would surface as an
                    # opaque KeyError deep inside the user fn
                    raise FugueInvalidOperation(
                        "compiled keyed map unavailable for partition keys "
                        f"{keys}: keys must be plain or dictionary-encoded "
                        "device columns (no nullable ints/maybe-NaN "
                        "floats), non-key columns must be un-encoded, and "
                        "encoded keys must keep their type in the output "
                        "schema. Use a pandas-annotated transformer for "
                        "these shapes."
                    )
        # general path: host-side partitioned execution, result back on
        # device; CONCURRENCY reflects the mesh, not the host engine
        host_engine = engine._host_engine
        if not hasattr(self, "_host_map"):
            self._host_map = PandasMapEngine(host_engine, parallelism_engine=engine)
        local = engine._host(df)
        res = self._host_map.map_dataframe(
            local,
            map_func,
            output_schema,
            partition_spec,
            on_init=on_init,
            map_func_format_hint=map_func_format_hint,
        )
        return engine.to_df(res)

    def _compiled_keyed_map(
        self,
        df: JaxDataFrame,
        fn: Callable,
        output_schema: Schema,
        partition_spec: PartitionSpec,
        on_init: Optional[Callable],
    ) -> DataFrame:
        """Keyed compiled map: groupby-apply that never leaves the device.

        The device-native answer to the reference's group-map path
        (``fugue_spark/execution_engine.py:192``): hash-repartition
        co-locates each key on one shard, ONE ``shard_map`` then sorts the
        shard by (validity, keys, presort), derives row-aligned contiguous
        ``__segments__`` ids, and traces the user fn over the sorted
        columns. The fn computes per-group results with
        ``jax.ops.segment_sum``-style reductions (``num_segments`` bounded
        by the static shard size) and returns a row-aligned dict. Padding
        rows sort to the shard tail, each in its own segment, and stay
        masked via ``__valid__``.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        engine: JaxExecutionEngine = self.execution_engine  # type: ignore
        keys = list(partition_spec.partition_by)
        dense = self._try_dense_keyed_map(
            df, fn, output_schema, partition_spec, keys, on_init
        )
        if dense is not None:
            return dense
        jdf: JaxDataFrame = engine.repartition(  # type: ignore[assignment]
            df, PartitionSpec(partition_spec, algo="hash")
        )
        if on_init is not None:
            on_init(0, jdf)
        sorts = partition_spec.get_sorts(jdf.schema, with_partition_keys=True)
        sort_items = tuple(sorts.items())
        mesh = jdf.mesh
        cache = engine._jit_cache
        cache_key = ("kmap", fn, mesh, sort_items, tuple(keys))
        if cache_key not in cache:

            def compute(cols: Dict[str, Any], valid: Any):
                def shard_fn(c: Dict[str, Any], v: Any):
                    # sort keys: valid rows first, then group keys (+presort)
                    ops: List[Any] = [jnp.logical_not(v)]
                    for name, asc in sort_items:
                        key = c[name]
                        if jnp.issubdtype(key.dtype, jnp.floating):
                            # NaN is the device NULL — order it FIRST inside
                            # ties, matching the host protocol's
                            # na_position="first" (asc or desc alike)
                            isnan = jnp.isnan(key)
                            ops.append(jnp.logical_not(isnan))
                            key = jnp.where(isnan, jnp.zeros((), key.dtype), key)
                            if not asc:
                                key = -key
                        elif not asc:
                            if key.dtype == jnp.bool_:
                                key = jnp.logical_not(key)
                            else:
                                key = ~key  # monotone reversal
                        ops.append(key)
                    names = list(c.keys())
                    res = jax.lax.sort(
                        tuple(ops) + tuple(c[n] for n in names) + (v,),
                        num_keys=len(ops),
                    )
                    payload = res[len(ops):]
                    sc = dict(zip(names, payload[: len(names)]))
                    sv = payload[len(names)]
                    # contiguous segment ids; every padding row becomes its
                    # own segment so group reductions never mix padding in
                    change = jnp.logical_not(sv)
                    for k in keys:
                        col = sc[k]
                        diff = jnp.concatenate(
                            [
                                jnp.ones((1,), dtype=bool),
                                col[1:] != col[:-1],
                            ]
                        )
                        change = jnp.logical_or(change, diff)
                    change = change.at[0].set(True)
                    seg = jnp.cumsum(change.astype(jnp.int32)) - 1
                    sc["__segments__"] = seg
                    sc["__valid__"] = sv
                    out = fn(sc)
                    out = {k2: v2 for k2, v2 in out.items() if k2 not in ("__segments__", "__valid__")}
                    out["__valid__"] = sv
                    return out

                return shard_map(
                    shard_fn,
                    mesh=mesh,
                    in_specs=(P(ROW_AXIS), P(ROW_AXIS)),
                    out_specs=P(ROW_AXIS),
                )(cols, valid)

            cache[cache_key] = jax.jit(compute)
        out = cache[cache_key](dict(jdf.device_cols), jdf.device_valid_mask())
        assert_or_throw(
            isinstance(out, dict),
            FugueInvalidOperation(
                "compiled transformer must return Dict[str, jax.Array]"
            ),
        )
        new_valid = out.pop("__valid__")
        n_in = next(iter(jdf.device_cols.values())).shape[0]
        missing = [n for n in output_schema.names if n not in out]
        assert_or_throw(
            len(missing) == 0,
            FugueInvalidOperation(
                f"compiled keyed transformer output missing columns {missing}"
            ),
        )
        same_len = all(v.shape[0] == n_in for v in out.values())
        assert_or_throw(
            same_len,
            FugueInvalidOperation(
                "compiled keyed transformers must return row-aligned arrays "
                "(same length as the sorted input shard)"
            ),
        )
        return JaxDataFrame(
            mesh=mesh,
            _internal=dict(
                device_cols={n: out[n] for n in output_schema.names},
                host_tbl=None,
                row_count=jdf.count(),
                valid_mask=new_valid,
                encodings=self._keyed_out_encodings(jdf, keys, output_schema),
                schema=output_schema,
            ),
        )

    def _try_dense_keyed_map(
        self,
        jdf: JaxDataFrame,
        fn: Callable,
        output_schema: Schema,
        partition_spec: PartitionSpec,
        keys: List[str],
        on_init: Optional[Callable],
    ) -> Optional[DataFrame]:
        """Sort-free, exchange-free keyed map (the dense plan).

        Integer keys with a bounded range map to globally-consistent dense
        segment ids (mixed radix over per-key spans); rows never move, and
        per-group reductions merge across shards INSIDE the user fn via the
        ``group_ops`` helpers (``lax.psum`` over the rows axis). This is
        the fast plan on every backend — sorts are the slow path on TPU,
        scatter reductions ride the VPU — and it costs zero data movement.

        Returns None when ineligible (presort, non-integer keys, unbounded
        range) — the caller falls back to the sorted plan.
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..constants import FUGUE_TPU_CONF_DENSE_MAP_RANGE
        from .group_ops import SEGMENT_SPACE, SEGMENTS, SPANS_SHARDS, VALID

        engine: JaxExecutionEngine = self.execution_engine  # type: ignore
        if len(partition_spec.presort) > 0:
            return None  # order inside groups requires the sorted plan
        if not all(
            np.issubdtype(np.dtype(jdf.device_cols[k].dtype), np.integer)
            for k in keys
        ):
            return None
        max_range = int(
            engine.conf.get(FUGUE_TPU_CONF_DENSE_MAP_RANGE, 1 << 20)
        )
        mesh = jdf.mesh
        valid = jdf.device_valid_mask()
        bounds: List[int] = []
        spans: List[int] = []
        for k in keys:
            enc = jdf.encodings.get(k)
            if enc is not None:
                # dict codes are bounded by construction: [-1, len) where
                # -1 is the NULL code — static metadata, no device probe
                lo, hi = -1, len(enc["dictionary"]) - 1
            else:
                lo, hi = jdf.key_range(k)  # cached per frame (one probe ever)
            if hi < lo:  # empty frame: degenerate single-bucket space
                lo, hi = 0, 0
            bounds.append(lo)
            spans.append(hi - lo + 1)
        total = 1
        for s in spans:
            total *= s
            if total > max_range:
                return None
        buckets = 1 << max(1, (total).bit_length())  # ≥ total+1: padding slot
        strides: List[int] = []
        acc = 1
        for s in reversed(spans):
            strides.append(acc)
            acc *= s
        strides = list(reversed(strides))
        if on_init is not None:
            on_init(0, jdf)
        cache = engine._jit_cache
        cache_key = ("kmapdense", fn, mesh, buckets, tuple(keys))
        if cache_key not in cache:

            def compute(cols: Dict[str, Any], v: Any, b: Any, st: Any, space: Any):
                def shard_fn(c: Dict[str, Any], v_: Any, b_: Any, st_: Any, sp_: Any):
                    ids = jnp.zeros(v_.shape, dtype=jnp.int64)
                    for i, k in enumerate(keys):
                        ids = ids + (c[k].astype(jnp.int64) - b_[i]) * st_[i]
                    ids = jnp.where(
                        v_, ids, jnp.int64(sp_.shape[0] - 1)
                    ).astype(jnp.int32)
                    sc = dict(c)
                    sc[SEGMENTS] = ids
                    sc[VALID] = v_
                    sc[SEGMENT_SPACE] = sp_
                    sc[SPANS_SHARDS] = sp_[:1]
                    out = fn(sc)
                    return {
                        k2: v2
                        for k2, v2 in out.items()
                        if k2 not in (SEGMENTS, VALID, SEGMENT_SPACE, SPANS_SHARDS)
                    }

                return shard_map(
                    shard_fn,
                    mesh=mesh,
                    in_specs=(P(ROW_AXIS), P(ROW_AXIS), P(), P(), P()),
                    out_specs=P(ROW_AXIS),
                )(cols, v, b, st, space)

            cache[cache_key] = jax.jit(compute)
        out = cache[cache_key](
            dict(jdf.device_cols),
            valid,
            jnp.asarray(bounds, dtype=jnp.int64),
            jnp.asarray(strides, dtype=jnp.int64),
            jnp.zeros((buckets,), dtype=jnp.bool_),
        )
        assert_or_throw(
            isinstance(out, dict),
            FugueInvalidOperation(
                "compiled transformer must return Dict[str, jax.Array]"
            ),
        )
        n_in = next(iter(jdf.device_cols.values())).shape[0]
        missing = [n for n in output_schema.names if n not in out]
        assert_or_throw(
            len(missing) == 0,
            FugueInvalidOperation(
                f"compiled keyed transformer output missing columns {missing}"
            ),
        )
        assert_or_throw(
            all(v2.shape[0] == n_in for v2 in out.values()),
            FugueInvalidOperation(
                "compiled keyed transformers must return row-aligned arrays"
            ),
        )
        # rows never moved: validity/count carry over unchanged
        return JaxDataFrame(
            mesh=mesh,
            _internal=dict(
                device_cols={n: out[n] for n in output_schema.names},
                host_tbl=None,
                row_count=jdf._row_count,
                valid_mask=jdf.valid_mask,
                encodings=self._keyed_out_encodings(jdf, keys, output_schema),
                schema=output_schema,
            ),
        )

    def _keyed_out_encodings(
        self, jdf: JaxDataFrame, keys: List[str], output_schema: Schema
    ) -> Dict[str, Any]:
        """Dictionary encodings to reattach to encoded partition keys that
        the UDF passed through (by contract) into the output."""
        return {
            k: dict(jdf.encodings[k])
            for k in keys
            if k in jdf.encodings and k in output_schema
        }

    def _compiled_map(
        self,
        df: JaxDataFrame,
        fn: Callable,
        output_schema: Schema,
        on_init: Optional[Callable],
    ) -> DataFrame:
        """ONE shard_map for the whole frame; user fn traced per shard.

        The input dict carries a reserved ``"__valid__"`` bool array marking
        real (non-padding) rows — functions doing per-shard reductions must
        mask with it; elementwise functions may ignore it.
        """
        import jax
        import numpy as np_
        from jax.sharding import PartitionSpec as P

        from ..ops.segment import _get_compiled_mask

        if on_init is not None:
            on_init(0, df)
        cols = dict(df.device_cols)
        assert_or_throw(
            len(cols) > 0,
            FugueInvalidOperation("no device columns to map on the compiled path"),
        )
        mesh = df.mesh
        cols["__valid__"] = df.device_valid_mask()
        cache = self.execution_engine._jit_cache  # type: ignore
        key = ("map", fn, mesh)
        if key not in cache:
            cache[key] = jax.jit(
                shard_map(
                    fn, mesh=mesh, in_specs=(P(ROW_AXIS),), out_specs=P(ROW_AXIS)
                )
            )
        mapped = cache[key]
        out = mapped(cols)
        assert_or_throw(
            isinstance(out, dict),
            FugueInvalidOperation("compiled transformer must return Dict[str, jax.Array]"),
        )
        out = {k: v for k, v in out.items() if k != "__valid__"}
        first = next(iter(out.values()))
        same_len = first.shape[0] == next(iter(cols.values())).shape[0]
        from ..constants import FUGUE_TPU_CONF_VALIDATE_COMPILED

        if self.execution_engine.conf.get(FUGUE_TPU_CONF_VALIDATE_COMPILED, False):
            self._validate_compiled(df, fn, cols, out, same_len)
        return JaxDataFrame(
            mesh=mesh,
            _internal=dict(
                device_cols=dict(out),
                host_tbl=None,
                row_count=df.count() if same_len else first.shape[0],
                valid_mask=df.valid_mask if same_len else None,
                schema=output_schema,
            ),
        )


    def _validate_compiled(
        self,
        df: JaxDataFrame,
        fn: Callable,
        cols: Dict[str, Any],
        out: Dict[str, Any],
        same_len: bool,
    ) -> None:
        """Debug cross-check (``fugue.tpu.validate_compiled``): run the UDF
        eagerly on ONE shard's VALID rows only — the reference semantics a
        correct, mask-honoring UDF must reproduce — and compare with the
        compiled output's block for that shard. The shard with the most
        padding is chosen (a mask-ignoring reduction only diverges where
        padding exists). Catches UDFs that reduce over padding rows because
        they ignored the ``__valid__`` mask."""
        import jax
        import jax.numpy as jnp
        import numpy as np_

        shards = num_row_shards(df.mesh)
        local_n = next(iter(cols.values())).shape[0] // shards
        valid_all = np_.asarray(jax.device_get(cols["__valid__"])).reshape(
            shards, local_n
        )
        per_shard = valid_all.sum(axis=1)
        # the shard with the most padding (possibly all-padding: the
        # reference then runs on zero rows — exactly what a correct UDF
        # must reproduce)
        s = int(per_shard.argmin())
        valid0 = valid_all[s]
        sl = slice(s * local_n, (s + 1) * local_n)
        ref_in = {
            k: jnp.asarray(np_.asarray(jax.device_get(v))[sl][valid0])
            for k, v in cols.items()
            if k != "__valid__"
        }
        ref_in["__valid__"] = jnp.ones(int(valid0.sum()), dtype=bool)
        try:
            ref_out = fn(ref_in)
        except Exception:  # collectives etc. can't run eagerly — skip
            self.execution_engine.log.debug(
                "validate_compiled: UDF not eagerly runnable; skipped"
            )
            return
        for name, arr in out.items():
            out_local = arr.shape[0] // shards
            block = np_.asarray(jax.device_get(arr))[
                s * out_local : (s + 1) * out_local
            ]
            if same_len:
                block = block[valid0]
            ref = np_.asarray(jax.device_get(ref_out[name]))
            ok = block.shape == ref.shape and (
                np_.allclose(block, ref, equal_nan=True)
                if np_.issubdtype(block.dtype, np_.floating)
                else bool((block == ref).all())
            )
            assert_or_throw(
                ok,
                FugueInvalidOperation(
                    f"compiled transformer output {name!r} differs from the "
                    "masked reference on shard 0 — the UDF likely ignores "
                    "the __valid__ mask and read padding rows"
                ),
            )


class JaxExecutionEngine(ExecutionEngine):
    """ExecutionEngine over a jax device mesh (name: ``"jax"`` / ``"tpu"``)."""

    def __init__(self, conf: Any = None, mesh: Any = None):
        super().__init__(conf)
        from ..constants import FUGUE_TPU_CONF_MESH_SHAPE

        if mesh is None:
            shape = self.conf.get_or_none(FUGUE_TPU_CONF_MESH_SHAPE, object)
            mesh = build_mesh(shape if shape is None else tuple(shape))
        self._mesh = mesh
        self._host_engine = NativeExecutionEngine(conf)
        # the host fallback engine executes the general (pandas) map path on
        # this engine's behalf — share one counter sink so recovery events
        # (retries, quarantines) are observable on the engine the user holds
        self._host_engine._resilience_stats = self.resilience_stats
        from .pipeline import JitCache, PipelineStats

        self._jit_cache: JitCache = JitCache()
        self._pipeline_stats = PipelineStats()
        from ..shuffle.stats import ShuffleStats

        # out-of-core hash shuffle (ISSUE 8): spill counters + the live
        # spill-dir set the resource sampler probes
        self._shuffle_stats = ShuffleStats()
        self._active_spill_dirs: set = set()
        self._last_join_strategy: Optional[str] = None
        # unified observability surface (ISSUE 3): every stats object this
        # engine owns lives in ONE registry behind engine.stats() /
        # engine.reset_stats(); the legacy attributes below stay as shims
        self.metrics.register("pipeline", lambda: self._pipeline_stats)
        self.metrics.register("jit_cache", lambda: self._jit_cache)
        self.metrics.register("shuffle", lambda: self._shuffle_stats)
        # host fallbacks, fetches and ingests, counted from their spans
        self.metrics.register("host_ops", get_host_op_stats())
        # record the resolved device budget + which detection source won
        # (conf / device_memory_stats / host_meminfo / fallback) so a
        # mis-detected budget is visible in engine.stats()["shuffle"]
        from ..shuffle.strategy import device_budget_info

        try:
            _budget, _budget_src = device_budget_info(self.conf)
            self._shuffle_stats.set_budget(_budget, _budget_src)
        except Exception:
            pass

    def _resource_probe_fns(self) -> Dict[str, Any]:
        # jax-engine occupancy for the continuous resource sampler
        # (ISSUE 6). Registered from the BASE constructor, before
        # _jit_cache/_pipeline_stats exist — probes run later, on the
        # sampler thread, so they guard with getattr.
        probes = dict(super()._resource_probe_fns())

        def _jit_entries(e: Any) -> float:
            cache = getattr(e, "_jit_cache", None)
            return float(len(cache)) if cache is not None else 0.0

        def _overlap(e: Any) -> float:
            ps = getattr(e, "_pipeline_stats", None)
            return float(ps.as_dict()["overlap_fraction"]) if ps is not None else 0.0

        def _spill_bytes(e: Any) -> float:
            # runs on the sampler thread while joins mutate the spill-dir
            # set — never let a race break the whole resource sampler
            try:
                dirs = getattr(e, "_active_spill_dirs", None)
                if not dirs:
                    return 0.0
                from ..shuffle.partitioner import spill_dir_bytes

                return float(spill_dir_bytes(dirs))
            except Exception:
                return 0.0

        probes["jit_cache_entries"] = _jit_entries
        probes["overlap_fraction"] = _overlap
        probes["shuffle_spill_bytes"] = _spill_bytes
        return probes

    @property
    def mesh(self) -> Any:
        return self._mesh

    @property
    def pipeline_stats(self) -> Any:
        """Ingest-pipeline observability (``fugue_tpu/jax/pipeline.py``):
        chunks prefetched, producer-wait vs consumer-wait seconds, and the
        measured overlap fraction, cumulative plus last run.

        Shim over ``engine.metrics`` — prefer ``engine.stats()["pipeline"]``."""
        return self._pipeline_stats

    @property
    def jit_cache_stats(self) -> Dict[str, int]:
        """Compile-cache hit/miss/entry counters for this engine.

        Shim over ``engine.metrics`` — prefer ``engine.stats()["jit_cache"]``."""
        return self._jit_cache.stats()

    @property
    def last_join_strategy(self) -> Optional[str]:
        """The rung the most recent device join took (``broadcast``,
        ``copartition``, ``device_exchange``...), None before any."""
        return self._last_join_strategy

    @property
    def is_distributed(self) -> bool:
        return True

    @property
    def log(self) -> logging.Logger:
        return logging.getLogger("JaxExecutionEngine")

    def create_default_map_engine(self) -> MapEngine:
        return JaxMapEngine(self)

    def create_default_sql_engine(self) -> SQLEngine:
        # bind the SQL facet to THIS engine (not the host fallback) so SQL
        # lowers onto the device verbs and conf lookups (e.g. the checkpoint
        # table warehouse) see this engine's live configuration
        from ..execution.native_execution_engine import _PlaceholderSQLEngine

        return _PlaceholderSQLEngine(self)

    def get_current_parallelism(self) -> int:
        return num_row_shards(self._mesh)

    @traced_verb("engine.to_df")
    def to_df(self, df: Any, schema: Any = None) -> DataFrame:
        if isinstance(df, JaxDataFrame):
            if schema is not None and df.schema != Schema(schema):
                # cast through arrow so the data actually converts
                return JaxDataFrame(
                    ArrowDataFrame(df.as_arrow().cast(Schema(schema).pa_schema)),
                    mesh=self._mesh,
                )
            return df
        from ..constants import FUGUE_TPU_CONF_INGEST_CACHE
        from .pipeline import prefetch_depth

        res = JaxDataFrame(
            df if isinstance(df, DataFrame) else self._host_engine.to_df(df, schema),
            mesh=self._mesh,
            ingest_cache=self.conf.get_or_none(
                FUGUE_TPU_CONF_INGEST_CACHE, bool
            ),
            ingest_prefetch_depth=prefetch_depth(self.conf),
            pipeline_stats=self._pipeline_stats,
        )
        src_meta = df.metadata if isinstance(df, DataFrame) and df.has_metadata else None
        if src_meta is not None:
            res.reset_metadata(src_meta)
        return res

    # ---- distribution primitives ------------------------------------------
    @traced_verb("engine.repartition")
    def repartition(self, df: DataFrame, partition_spec: PartitionSpec) -> DataFrame:
        """Physically move rows between shards with an all-to-all exchange.

        ``hash`` (or keyed default) co-locates equal keys on one shard —
        the basis for shuffle joins and co-sharded cotransforms; ``even``
        (or key-less default) rebalances row counts; ``rand`` scatters
        randomly; ``coarse`` is metadata-only by definition. Frames with
        host-resident columns keep their layout (logical partitioning in
        map/aggregate still honors the spec) — that case logs a warning.
        Matches the reference's per-backend repartition algorithms
        (``fugue_spark/_utils/partition.py:15-117``).
        """
        from ..ops.shuffle import compute_dest, exchange_rows

        if partition_spec is None or partition_spec.empty:
            return df
        algo = partition_spec.algo
        by = list(partition_spec.partition_by)
        if algo == "":
            algo = "hash" if len(by) > 0 else "even"
        if algo == "hash" and len(by) == 0:
            algo = "even"
        if algo == "hash":
            # out-of-core layout (ISSUE 8): a one-pass stream, or a
            # bounded frame whose estimate exceeds the device budget,
            # hash-partitions through the on-disk spill partitioner —
            # every key ends up in exactly ONE chunk of the result
            # stream, so arbitrarily large PartitionSpec maps stay
            # key-complete without ever being device-resident at once
            from ..shuffle.strategy import (
                device_budget_bytes,
                estimate_frame_bytes,
                shuffle_enabled,
            )
            from .streaming import is_stream_frame

            if shuffle_enabled(self.conf):
                streaming = is_stream_frame(df)
                est = None if streaming else estimate_frame_bytes(df)
                if streaming or (
                    est is not None and est > device_budget_bytes(self.conf)
                ):
                    from ..shuffle.join import spill_repartition

                    try:
                        num = int(partition_spec.num_partitions or "0")
                    except ValueError:
                        num = 0
                    res = spill_repartition(self, df, by, num=num)
                    if res is not None:
                        return res
        jdf = self.to_df(df)
        if algo == "coarse":
            return jdf
        device_ok = (
            isinstance(jdf, JaxDataFrame)
            and len(jdf.device_cols) > 0
            and jdf.host_table is None
            and (algo != "hash" or all(k in jdf.device_cols for k in by))
        )
        if not device_ok:
            self.log.warning(
                "repartition(%s): frame has host-resident columns; physical "
                "layout unchanged (logical partitioning still applies)",
                algo,
            )
            return jdf
        valid = jdf.device_valid_mask()
        dest = compute_dest(
            self._mesh,
            algo,
            [jdf.device_cols[k] for k in by] if algo == "hash" else [],
            valid,
        )
        return self._exchange_to(jdf, dest, valid)

    def _repartition_single(self, df: DataFrame) -> "JaxDataFrame":
        """Move every row to shard 0 — the one-partition physical layout
        behind global (no PARTITION BY) window evaluation. Fully-device
        frames only; callers gate on that."""
        from ..ops.shuffle import compute_dest

        jdf = self.to_df(df)
        valid = jdf.device_valid_mask()
        dest = compute_dest(self._mesh, "single", [], valid)
        return self._exchange_to(jdf, dest, valid)

    def _exchange_to(
        self, jdf: "JaxDataFrame", dest: Any, valid: Any
    ) -> "JaxDataFrame":
        """All-to-all exchange of a device frame to per-row destinations."""
        from ..ops.shuffle import exchange_rows

        # null masks are row-aligned — they travel with their columns
        mp = _safe_prefix("__mask__", jdf.schema.names)
        payload = dict(jdf.device_cols)
        for c, m in jdf.null_masks.items():
            payload[f"{mp}{c}"] = m
        new_payload, new_valid, _ = exchange_rows(
            self._mesh, payload, valid, dest
        )
        new_cols = {c: new_payload[c] for c in jdf.device_cols}
        new_masks = {
            c: new_payload[f"{mp}{c}"] for c in jdf.null_masks
        }
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols=new_cols,
                host_tbl=None,
                row_count=jdf.count(),
                valid_mask=new_valid,
                nan_cols=jdf._nan_cols,
                encodings=dict(jdf.encodings),
                null_masks=new_masks,
                schema=jdf.schema,
            ),
        )

    @traced_verb("engine.broadcast")
    def broadcast(self, df: DataFrame) -> DataFrame:
        import jax

        jdf = self.to_df(df)
        rep = replicated_sharding(self._mesh)
        cols = {k: jax.device_put(v, rep) for k, v in jdf.device_cols.items()}
        # a filtered frame carries an explicit hole-y valid mask; it must
        # travel with the rows or broadcasting silently re-validates them
        vm = jdf.valid_mask
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols=cols,
                host_tbl=jdf.host_table,
                row_count=jdf.count(),
                valid_mask=None if vm is None else jax.device_put(vm, rep),
                nan_cols=jdf._nan_cols,
                encodings=dict(jdf.encodings),
                null_masks={
                    k: jax.device_put(v, rep) for k, v in jdf.null_masks.items()
                },
                schema=jdf.schema,
            ),
        )

    @traced_verb("engine.persist")
    def persist(self, df: DataFrame, lazy: bool = False, **kwargs: Any) -> DataFrame:
        import jax

        jdf = self.to_df(df)
        if not lazy:
            for v in jdf.device_cols.values():
                jax.block_until_ready(v)
        if df.has_metadata:
            jdf.reset_metadata(df.metadata)
        return jdf

    # ---- relational ops ----------------------------------------------------
    @traced_verb("engine.filter")
    def filter(self, df: DataFrame, condition: Any, _plan: Any = None) -> DataFrame:
        """Device filter: the condition becomes a validity mask — no rows
        move, downstream device ops and host conversion honor the mask.

        Runs with SQL three-valued NULL semantics (rows where the predicate
        is NULL are dropped): NaN floats and per-column null masks are
        NULLs, and predicates on dictionary-encoded string columns evaluate
        host-side over the dictionary into a lookup table gathered by code.
        ``_plan`` lets ``select`` reuse its already-computed predicate plan.
        """
        from ..column.jax_eval import device_predicate_plan

        jdf = self.to_df(df)
        reason = "host_frame"
        if (
            isinstance(jdf, JaxDataFrame)
            and len(jdf.device_cols) > 0
            and jdf.host_table is None
        ):
            reason = "where_not_lowered"
            plan = (
                _plan
                if _plan is not None
                else device_predicate_plan(
                    condition, jdf.device_cols, jdf.encodings
                )
            )
            if plan is not None:
                import jax

                tables, cond = plan  # datetime literals rewritten to epochs
                uuids = tuple(sorted(tables.keys()))
                names = {u: tables[u][0] for u in uuids}
                code_cols = frozenset(
                    c for c, e in jdf.encodings.items() if e["kind"] == "dict"
                )
                cache_key = (
                    "filter3v", cond.__uuid__(), jdf.mesh, uuids, code_cols
                )
                if cache_key not in self._jit_cache:

                    def apply_mask(
                        cols: Dict[str, Any],
                        masks: Dict[str, Any],
                        tarrs: Any,
                        valid: Any,
                    ) -> Any:
                        import jax.numpy as jnp

                        from ..column.jax_eval import evaluate_jnp_3v

                        dt = {u: (names[u], t) for u, t in zip(uuids, tarrs)}
                        v, nl = evaluate_jnp_3v(
                            cols, masks, dt, cond, code_cols
                        )
                        return (
                            valid
                            & jnp.asarray(v, dtype=bool)
                            & jnp.logical_not(nl)
                        )

                    self._jit_cache[cache_key] = jax.jit(apply_mask)
                new_mask = self._jit_cache[cache_key](
                    dict(jdf.device_cols),
                    dict(jdf.null_masks),
                    tuple(tables[u][1] for u in uuids),
                    jdf.device_valid_mask(),
                )
                return JaxDataFrame(
                    mesh=self._mesh,
                    _internal=dict(
                        device_cols=dict(jdf.device_cols),
                        host_tbl=None,
                        row_count=-1,  # computed lazily from the mask
                        valid_mask=new_mask,
                        nan_cols=jdf._nan_cols,
                        encodings=dict(jdf.encodings),
                        null_masks=dict(jdf.null_masks),
                        schema=jdf.schema,
                    ),
                )
        with _host_op("filter", reason) as sp:
            return self._back(self._host_engine.filter(self._host(df, sp), condition))

    @traced_verb("engine.fused")
    def fused_apply(self, df: DataFrame, steps: Any) -> DataFrame:
        """Fused chain execution (``fugue_tpu/plan/fused.py``):

        - one-pass streams apply the steps per chunk INSIDE the chunk
          producer (rows filtered out are never H2D-transferred and the
          stream stays out-of-core);
        - fully-device frames compile the whole chain — the Kleene-AND of
          every filter plus all projections — into ONE jitted step (no
          intermediate device buffers, one kernel launch per chain);
        - anything else falls back to sequential verb application, which
          is exactly what the unfused chain would have run.
        """
        from .streaming import is_stream_frame, streaming_fused_steps

        if is_stream_frame(df):
            return streaming_fused_steps(self, df, steps)
        jdf = self.to_df(df)
        res = self._try_fused_device(jdf, steps)
        if res is not None:
            return res
        return super().fused_apply(jdf, steps)

    def _try_fused_device(self, jdf: DataFrame, steps: Any) -> Optional[DataFrame]:
        """Single-jit execution of a composed chain, or None when any
        step resists composition/device lowering (sequential fallback
        keeps identical semantics)."""
        from ..column.jax_eval import device_predicate_plan
        from ..plan.fused import compose_steps

        if (
            not isinstance(jdf, JaxDataFrame)
            or len(jdf.device_cols) == 0
            or jdf.host_table is not None
        ):
            return None
        composed = compose_steps(list(jdf.schema.names), steps)
        if composed is None:
            return None
        pred, outputs = composed
        passthrough_ids = {
            id(c) for c in outputs if _is_passthrough(c, jdf.device_cols)
        }
        computed = [c for c in outputs if id(c) not in passthrough_ids]
        plain_cols = {
            k: v
            for k, v in jdf.device_cols.items()
            if k not in jdf.encodings and k not in jdf.null_masks
        }
        if not all(can_evaluate_on_device(c, plain_cols) for c in computed):
            return None
        plan = None
        if pred is not None:
            plan = device_predicate_plan(pred, jdf.device_cols, jdf.encodings)
            if plan is None:
                return None
        import jax

        tables, cond = plan if plan is not None else ({}, None)
        uuids = tuple(sorted(tables.keys()))
        names = {u: tables[u][0] for u in uuids}
        code_cols = frozenset(
            c for c, e in jdf.encodings.items() if e["kind"] == "dict"
        )
        cache_key = (
            "fused",
            "" if cond is None else cond.__uuid__(),
            tuple(c.__uuid__() for c in computed),
            jdf.mesh,
            uuids,
            code_cols,
        )
        if cache_key not in self._jit_cache:

            def run(
                cols: Dict[str, Any],
                masks: Dict[str, Any],
                tarrs: Any,
                valid: Any,
            ) -> Any:
                import jax.numpy as jnp

                from ..column.jax_eval import evaluate_jnp_3v

                if cond is not None:
                    dt = {u: (names[u], t) for u, t in zip(uuids, tarrs)}
                    v, nl = evaluate_jnp_3v(cols, masks, dt, cond, code_cols)
                    valid = (
                        valid & jnp.asarray(v, dtype=bool) & jnp.logical_not(nl)
                    )
                outs = {}
                for c in computed:
                    v = evaluate_jnp(cols, c)
                    if not hasattr(v, "shape") or getattr(v, "ndim", 0) == 0:
                        n = next(iter(cols.values())).shape[0]
                        v = jnp.full((n,), v)
                    outs[c.output_name] = v
                return outs, valid

            self._jit_cache[cache_key] = jax.jit(run)
        outs, new_valid = self._jit_cache[cache_key](
            dict(jdf.device_cols),
            dict(jdf.null_masks),
            tuple(tables[u][1] for u in uuids),
            jdf.device_valid_mask(),
        )
        out_cols: Dict[str, Any] = {}
        out_enc: Dict[str, Any] = {}
        out_masks: Dict[str, Any] = {}
        fields = []
        for c in outputs:
            name = c.output_name
            if id(c) in passthrough_ids:
                src = c.name
                out_cols[name] = jdf.device_cols[src]
                if src in jdf.encodings:
                    out_enc[name] = jdf.encodings[src]
                if src in jdf.null_masks:
                    out_masks[name] = jdf.null_masks[src]
                fields.append(pa.field(name, jdf.schema[src].type))
            else:
                out_cols[name] = outs[name]
                t = c.infer_type(jdf.schema)
                fields.append(
                    pa.field(
                        name,
                        t
                        if t is not None
                        else pa.from_numpy_dtype(
                            np.asarray(outs[name]).dtype
                        ),
                    )
                )
        from ..column.expressions import _NamedColumnExpr as _Named

        nan_cols: Optional[set] = None
        if jdf._nan_cols is not None:
            nan_cols = set()
            for c in outputs:
                if isinstance(c, _Named) and c.as_type is None:
                    if c.name in jdf._nan_cols:
                        nan_cols.add(c.output_name)
                else:
                    arr = out_cols[c.output_name]
                    if np.issubdtype(np.dtype(arr.dtype), np.floating):
                        nan_cols.add(c.output_name)
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols=out_cols,
                host_tbl=None,
                row_count=jdf._row_count if pred is None else -1,
                valid_mask=jdf.valid_mask if pred is None else new_valid,
                nan_cols=nan_cols,
                encodings=out_enc,
                null_masks=out_masks,
                schema=Schema(fields),
            ),
        )

    def lowered_segment(
        self,
        dfs: List[DataFrame],
        steps: Any,
        terminal: Any,
        partition_spec: Optional[PartitionSpec],
        fingerprint: str = "",
    ) -> DataFrame:
        """Execute a lowered plan segment (``fugue_tpu/plan/lowering.py``)
        as ONE compiled SPMD program where eligible:

        - stream → fused chain → dense aggregate (the flagship): each raw
          chunk goes H2D once and a single jitted ``shard_map`` program
          runs predicate + projections + dense-bucket kernel (cross-shard
          ``psum``/``pmin``/``pmax`` inlined as in-program collectives) +
          donated accumulator fold — one jit-cache entry labeled
          ``segment:<fingerprint>`` for the whole pipeline segment;
        - device-resident frame → fused chain → dense aggregate: the
          whole segment is one jitted program (chain + kernel + finish);
        - stream → fused chain → take / distinct / broadcast-join probe:
          the chain runs as one device program per chunk, survivors feed
          the terminal's running buffer / probe.

        Any refusal (non-composable step, host-only type, ineligible
        aggregate plan, ...) falls back per segment to the per-verb path —
        ``fused_apply`` + the terminal verb, bit-identical to the
        unlowered task pair, same ``engine.<verb>`` spans. A lowered run
        executes under ONE ``plan.segment`` span instead.
        """
        from ..obs import get_tracer

        terminal = tuple(terminal)
        runner = None
        try:
            runner = self._plan_lowered_segment(
                dfs, list(steps), terminal, partition_spec, fingerprint
            )
        except Exception as ex:  # planning must never break execution
            self.log.warning(
                "segment lowering refused with an error (%s: %s); "
                "falling back to the per-verb path",
                type(ex).__name__,
                ex,
            )
            runner = None
        if runner is not None:
            tracer = get_tracer()
            with tracer.span(
                "plan.segment",
                cat="plan",
                annotate=True,
                segment=fingerprint,
                terminal=terminal[0],
                steps=len(steps),
            ):
                res = runner()
            self.plan_stats.inc("segments_executed")
            return res
        self.plan_stats.inc("segments_fallback")
        return super().lowered_segment(
            dfs, steps, terminal, partition_spec, fingerprint=fingerprint
        )

    def _plan_lowered_segment(
        self,
        dfs: List[DataFrame],
        steps: List[Any],
        terminal: Tuple,
        partition_spec: Optional[PartitionSpec],
        fingerprint: str,
    ) -> Optional[Callable[[], DataFrame]]:
        """Phase-1 planning: return a zero-arg runner when the segment
        lowers, None to fall back. Planning never consumes stream data."""
        from .streaming import (
            is_stream_frame,
            plan_lowered_steps_stream,
            plan_streaming_lowered_aggregate,
            streaming_distinct,
            streaming_take,
        )

        if len(steps) == 0:
            return None
        kind = terminal[0]
        if kind == "aggregate":
            keys = (
                list(partition_spec.partition_by)
                if partition_spec is not None
                else []
            )
            agg_cols = list(terminal[1])
            df = dfs[0]
            if is_stream_frame(df):
                return plan_streaming_lowered_aggregate(
                    self, df, steps, keys, agg_cols, fingerprint
                )
            return self._plan_lowered_bounded_aggregate(
                df, steps, keys, agg_cols, fingerprint
            )
        if kind == "take":
            df = dfs[0]
            if not is_stream_frame(df):
                return None
            mk = plan_lowered_steps_stream(self, df, steps, fingerprint)
            if mk is None:
                return None
            return lambda: streaming_take(
                self, mk(), terminal[1], terminal[2], terminal[3], partition_spec
            )
        if kind == "distinct":
            df = dfs[0]
            if not is_stream_frame(df):
                return None
            mk = plan_lowered_steps_stream(self, df, steps, fingerprint)
            if mk is None:
                return None
            return lambda: streaming_distinct(self, mk())
        if kind == "join":
            probe = terminal[3]
            df = dfs[probe]
            build = dfs[1 - probe]
            if not is_stream_frame(df) or is_stream_frame(build):
                return None
            mk = plan_lowered_steps_stream(self, df, steps, fingerprint)
            if mk is None:
                return None

            def run_join() -> DataFrame:
                ldf = mk()
                d1, d2 = (ldf, build) if probe == 0 else (build, ldf)
                return self.join(d1, d2, how=terminal[1], on=list(terminal[2]))

            return run_join
        return None

    def _plan_lowered_bounded_aggregate(
        self,
        df: DataFrame,
        steps: List[Any],
        keys: List[str],
        agg_cols: List[ColumnExpr],
        fingerprint: str,
    ) -> Optional[Callable[[], DataFrame]]:
        """Lowered (chain → dense aggregate) over a fully device-resident
        frame: predicate, projections, dense-bucket kernel (in-program
        cross-shard collectives) and the on-device finish trace into ONE
        jitted program — no intermediate frame, no host roundtrip.
        Eligibility mirrors ``_try_dense_device_aggregate`` with the
        chain's key/value sources required to be plain (un-encoded,
        un-masked) columns or device-computable expressions over them."""
        from ..column.jax_eval import device_predicate_plan
        from ..plan.fused import compose_steps
        from ..ops.segment import (
            _DENSE_MAX_RANGE,
            _DENSE_SUM_BACKEND,
            _get_compiled_dense,
            dense_buckets,
        )
        from .streaming import _np_dtype_of

        if len(keys) != 1:
            return None
        jdf = self.to_df(df)
        if (
            not isinstance(jdf, JaxDataFrame)
            or len(jdf.device_cols) == 0
            or jdf.host_table is not None
        ):
            return None
        composed = compose_steps(list(jdf.schema.names), steps)
        if composed is None:
            return None
        pred, outputs = composed
        outs_by_name = {e.output_name: e for e in outputs}
        if len(outs_by_name) != len(outputs):
            return None
        plain_cols = {
            k: v
            for k, v in jdf.device_cols.items()
            if k not in jdf.encodings and k not in jdf.null_masks
        }
        import jax
        import jax.numpy as jnp

        zcols = {
            k: jnp.zeros((0,), dtype=np.dtype(v.dtype))
            for k, v in plain_cols.items()
        }
        passthrough_ids = {
            id(e) for e in outputs if _is_passthrough(e, jdf.device_cols)
        }
        fields: List[pa.Field] = []
        out_np: Dict[str, np.dtype] = {}
        for e in outputs:
            name = e.output_name
            if id(e) in passthrough_ids:
                fields.append(pa.field(name, jdf.schema[e.name].type))
                continue
            if not can_evaluate_on_device(e, plain_cols):
                return None
            try:
                arr = jnp.asarray(evaluate_jnp(zcols, e))
            except Exception:
                return None
            out_np[name] = np.dtype(arr.dtype)
            t = e.infer_type(jdf.schema)
            fields.append(
                pa.field(
                    name, t if t is not None else pa.from_numpy_dtype(out_np[name])
                )
            )
        probe_schema = Schema(fields)
        empty = pa.Table.from_pylist([], schema=probe_schema.pa_schema)
        try:
            jdf0 = JaxDataFrame(ArrowDataFrame(empty), mesh=self._mesh)
        except Exception:
            return None
        plan = _device_agg_plan(jdf0, keys, agg_cols)
        if (
            isinstance(plan, str)
            or plan["virtual"]
            or plan["dict_srcs"]
            or plan["masked_srcs"]
            or any(p.get("kind") not in ("pass", "avg") for p in plan["post"])
        ):
            return None
        key = keys[0]
        key_expr = outs_by_name.get(key)
        from ..column.expressions import _NamedColumnExpr as _Named

        if (
            not isinstance(key_expr, _Named)
            or key_expr.wildcard
            or key_expr.as_type is not None
        ):
            return None
        raw_key = key_expr.name
        if raw_key not in plain_cols:
            return None
        key_np = np.dtype(jdf.device_cols[raw_key].dtype)
        if key_np.kind not in ("i", "u"):
            return None
        srcs = sorted({s for _, _, s in plan["aggs"]})
        actual: Dict[str, np.dtype] = {}
        src_expr: Dict[str, Any] = {}
        for s in srcs:
            e = outs_by_name.get(s)
            if e is None:
                return None
            if id(e) in passthrough_ids:
                if e.name not in plain_cols:
                    return None  # masked/encoded source would lose its NULLs
                actual[s] = np.dtype(jdf.device_cols[e.name].dtype)
            else:
                actual[s] = out_np[s]
            if actual[s].kind not in ("i", "u", "f"):
                return None
            src_expr[s] = e
        del jdf0
        # range over the RAW key column (pre-filter superset — correct,
        # possibly more buckets; the cached frame probe pays once)
        kmin, kmax = jdf.key_range(raw_key)
        rng = kmax - kmin + 1
        if not (0 < rng <= _DENSE_MAX_RANGE):
            return None
        predicted: Dict[str, np.dtype] = {
            name: (np.dtype(np.int64) if agg == "count" else actual[src])
            for name, agg, src in plan["aggs"]
        }
        spec_rows = _dense_finish_spec(plan, predicted)
        if spec_rows is None:
            return None
        tables: Dict[str, Any] = {}
        cond = None
        if pred is not None:
            pplan = device_predicate_plan(pred, jdf.device_cols, jdf.encodings)
            if pplan is None:
                return None
            tables, cond = pplan
        uuids = tuple(sorted(tables.keys()))
        tnames = {u: tables[u][0] for u in uuids}
        code_cols = frozenset(
            c for c, e in jdf.encodings.items() if e["kind"] == "dict"
        )
        vidx = {s: i for i, s in enumerate(srcs)}
        agg_sig = tuple(
            (name, agg, vidx[src], actual[src].kind == "f")
            for name, agg, src in plan["aggs"]
        )
        buckets = dense_buckets(rng)
        kernel = _get_compiled_dense(self._mesh, buckets, agg_sig)
        kmin_s = np.int64(kmin)
        label = f"segment:{fingerprint or 'anon'}"
        cache_key = (
            label,
            self._mesh,
            buckets,
            agg_sig,
            spec_rows,
            key_np.str,
            kmin,
            uuids,
            code_cols,
            _DENSE_SUM_BACKEND[0],
        )

        def run() -> DataFrame:
            from ..column.jax_eval import evaluate_jnp as _ev
            from ..column.jax_eval import evaluate_jnp_3v as _ev3

            if cache_key not in self._jit_cache:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                arr_names = tuple(s[0] for s in agg_sig)
                fin = self._make_dense_finish(
                    buckets, arr_names, spec_rows, key_np.str
                )

                def prog(
                    cols: Dict[str, Any],
                    masks: Dict[str, Any],
                    tarrs: Any,
                    valid: Any,
                ):
                    import jax.numpy as _jnp

                    if cond is not None:
                        dt = {u: (tnames[u], t) for u, t in zip(uuids, tarrs)}
                        pv, nl = _ev3(cols, masks, dt, cond, code_cols)
                        valid = (
                            valid
                            & _jnp.asarray(pv, dtype=bool)
                            & _jnp.logical_not(nl)
                        )
                    karr = cols[raw_key]
                    vals = []
                    for s in srcs:
                        e = src_expr[s]
                        if id(e) in passthrough_ids:
                            a = cols[e.name]
                        else:
                            a = _ev(cols, e)
                            if (
                                not hasattr(a, "shape")
                                or getattr(a, "ndim", 0) == 0
                            ):
                                a = _jnp.full((valid.shape[0],), a)
                            a = _jnp.asarray(a).astype(actual[s])
                        vals.append(a)
                    outs = kernel(karr, kmin_s, *vals, valid)
                    return fin(kmin_s, outs[0], *outs[1:])

                self._jit_cache[cache_key] = jax.jit(
                    prog,
                    out_shardings=NamedSharding(self._mesh, P(ROW_AXIS)),
                )
            outs = self._jit_cache[cache_key](
                dict(jdf.device_cols),
                dict(jdf.null_masks),
                tuple(tables[u][1] for u in uuids),
                jdf.device_valid_mask(),
            )
            device_cols = {key: outs[0]}
            for (_, name, _, _), arr in zip(spec_rows, outs[2:]):
                device_cols[name] = arr
            return JaxDataFrame(
                mesh=self._mesh,
                _internal=dict(
                    device_cols=device_cols,
                    host_tbl=None,
                    row_count=-1,
                    valid_mask=outs[1],
                    schema=plan["schema"],
                ),
            )

        return run

    def _host(self, df: DataFrame, sp: Any = NULL_SPAN) -> DataFrame:
        """The host copy of ``df`` for a host-engine call; inside a live
        ``host.op`` span ``sp`` its rows count toward ``rows_in``."""
        local = df.as_local_bounded() if isinstance(df, JaxDataFrame) else self._host_engine.to_df(df)
        if sp is not NULL_SPAN:
            sp.add(rows_in=local.count())
        return local

    def _back(self, df: DataFrame) -> DataFrame:
        return self.to_df(df)

    def join(self, df1, df2, how: str, on=None) -> DataFrame:
        """Hash joins run on device (``ops/join.py``): inner / left_outer /
        left_semi / left_anti, multi-key, unique OR duplicate right keys
        (the 1:N/N:M expansion kernel). Strategy ladder (docs/shuffle.md),
        decided from size estimates + conf by ``shuffle.strategy``:
        **broadcast** for small right sides, **copartition** (in-device
        all-to-all + shard-local probe) when both sides fit the device
        budget at once, **device_exchange** (staged one-hop-at-a-time
        on-device exchange, ``fugue_tpu/shuffle/exchange.py``) when the
        sides exceed the per-device budget but fit aggregate mesh
        memory, **shuffle_spill** (on-disk hash buckets joined one pair
        at a time, ``fugue_tpu/shuffle/``) past it — the chosen
        strategy is an attr on the ``engine.join`` span. right_outer
        mirrors left_outer; full_outer composes left_outer ∪ NULL-extended
        anti; cross runs through the expansion kernel on a constant key.
        Host fallback: host-resident frames, keys the preparers can't
        align, and expansions past the per-shard slot budget."""
        from ..obs import get_tracer

        with get_tracer().span("engine.join", cat="engine", annotate=True) as sp:
            return self._join_impl(df1, df2, how, on, sp)

    def _join_impl(self, df1, df2, how: str, on, sp) -> DataFrame:
        from ..dataframe.utils import parse_join_type
        from ..shuffle.strategy import (
            choose_join_strategy,
            estimate_frame_bytes,
            estimate_frame_rows,
            shuffle_enabled,
        )
        from .streaming import is_stream_frame, streaming_hash_join

        self._last_join_strategy = None
        # adaptive execution (docs/tuning.md): inside an enabled run scope
        # the tuner substitutes OBSERVED side cardinalities from previous
        # runs of this plan where the static estimate is unknowable, and
        # carries the calibrated bucket count into the spill shuffle; the
        # runtime decision function below stays authoritative either way
        tuner = getattr(self, "tuner", None)
        if is_stream_frame(df1) or is_stream_frame(df2):
            tune = (
                tuner.join_params(None, None, None)[3]
                if tuner is not None
                else None
            )
            # one-pass input: bounded-memory broadcast-hash join first
            res = streaming_hash_join(self, df1, df2, how, on)
            if res is not None:
                sp.set(strategy="stream")
                return res
            if shuffle_enabled(self.conf):
                # the spill shuffle consumes the stream chunk-by-chunk
                # too — materializing (the unbounded-memory hazard) is
                # now the LAST resort, not the only remaining option
                from ..shuffle.join import shuffle_spill_join

                res = shuffle_spill_join(self, df1, df2, how, on, tune=tune)
                if res is not None:
                    sp.set(
                        strategy="shuffle_spill",
                        reason="stream ineligible for the streaming join plan",
                    )
                    return res
            self.log.warning(
                "streaming join ineligible for this plan; materializing "
                "the stream"
            )
        else:
            est_l = estimate_frame_bytes(df1)
            est_r = estimate_frame_bytes(df2)
            est_rr = estimate_frame_rows(df2)
            tune = None
            if tuner is not None:
                est_l, est_r, est_rr, tune = tuner.join_params(
                    est_l, est_r, est_rr
                )
            dec = choose_join_strategy(
                self.conf,
                est_l,
                est_r,
                est_rr,
                n_shards=num_row_shards(self._mesh),
            )
            if dec.strategy == "device_exchange":
                # sides past the per-device budget but within aggregate
                # mesh memory: rows stay device-resident and move with
                # the staged one-hop schedule (shuffle/exchange.py) —
                # zero host round trips between partition and kernel
                res = self._try_device_exchange(df1, df2, how, on, tune)
                if res is not None:
                    sp.set(strategy="device_exchange", reason=dec.reason)
                    self._shuffle_stats.inc("device_exchange_joins")
                    return res
                # ineligible frames (host-resident columns, keys the
                # preparers can't align, cross joins): spill remains the
                # bit-identical fallback — same discipline as over-budget
                self._shuffle_stats.inc("device_exchange_fallbacks")
                if shuffle_enabled(self.conf):
                    from ..shuffle.join import shuffle_spill_join

                    res = shuffle_spill_join(self, df1, df2, how, on, tune=tune)
                    if res is not None:
                        sp.set(
                            strategy="shuffle_spill",
                            reason=f"device_exchange ineligible; {dec.reason}",
                        )
                        return res
            elif dec.strategy == "shuffle_spill" and shuffle_enabled(self.conf):
                from ..shuffle.join import shuffle_spill_join

                res = shuffle_spill_join(self, df1, df2, how, on, tune=tune)
                if res is not None:
                    sp.set(strategy="shuffle_spill", reason=dec.reason)
                    return res
        jt = parse_join_type(how)
        if jt in ("inner", "left_outer", "left_semi", "left_anti"):
            kernel_how = {
                "inner": "inner",
                "left_outer": "left_outer",
                "left_semi": "semi",
                "left_anti": "anti",
            }[jt]
            res = self._join_device(df1, df2, kernel_how, on)
            if res is not None:
                sp.set(strategy=self._last_join_strategy or "device")
                return res
        elif jt == "right_outer":
            # mirrored left_outer, columns re-ordered to the contract schema
            res = self._join_device(df2, df1, "left_outer", on)
            if res is not None:
                from ..dataframe.utils import get_join_schemas

                _, out_schema = get_join_schemas(
                    self.to_df(df1), self.to_df(df2), how="right_outer", on=on
                )
                if list(res.schema.names) != out_schema.names:
                    res = res[out_schema.names]  # type: ignore[index]
                sp.set(strategy=self._last_join_strategy or "device")
                return res
        elif jt == "full_outer":
            res = self._full_outer_device(df1, df2, on)
            if res is not None:
                sp.set(strategy=self._last_join_strategy or "device")
                return res
        elif jt == "cross":
            res = self._cross_device(df1, df2)
            if res is not None:
                sp.set(strategy="broadcast")
                return res
        sp.set(strategy="host")
        with _host_op("join", "device_join_refused", jt) as hp:
            return self._back(
                self._host_engine.join(self._host(df1, hp), self._host(df2, hp), how=how, on=on)
            )

    def _try_device_exchange(self, df1, df2, how: str, on, tune) -> Optional[DataFrame]:
        """Run the join through the device_exchange rung: the same device
        join-type dispatch as the generic ladder, but the co-partition
        step uses the STAGED exchange (and broadcast is skipped — the
        right side already failed the per-device budget). None → caller
        falls back to spill, bit-identically."""
        from ..dataframe.utils import parse_join_type

        jt = parse_join_type(how)
        if jt in ("inner", "left_outer", "left_semi", "left_anti"):
            kernel_how = {
                "inner": "inner",
                "left_outer": "left_outer",
                "left_semi": "semi",
                "left_anti": "anti",
            }[jt]
            return self._join_device(
                df1, df2, kernel_how, on, exchange_staged=True, tune=tune
            )
        if jt == "right_outer":
            res = self._join_device(
                df2, df1, "left_outer", on, exchange_staged=True, tune=tune
            )
            if res is not None:
                from ..dataframe.utils import get_join_schemas

                _, out_schema = get_join_schemas(
                    self.to_df(df1), self.to_df(df2), how="right_outer", on=on
                )
                if list(res.schema.names) != out_schema.names:
                    res = res[out_schema.names]  # type: ignore[index]
            return res
        if jt == "full_outer":
            return self._full_outer_device(
                df1, df2, on, exchange_staged=True, tune=tune
            )
        return None  # cross: replication-shaped, nothing to exchange

    def _full_outer_device(
        self, df1, df2, on, exchange_staged: bool = False, tune=None
    ) -> Optional[DataFrame]:
        """full_outer = left_outer(L,R) ∪ (anti(R,L) with NULL left
        values) — composed from device verbs, so it inherits all their
        representations (dictionaries, epochs, masks)."""
        from ..dataframe.utils import get_join_schemas

        try:
            _, out_schema = get_join_schemas(
                self.to_df(df1), self.to_df(df2), how="full_outer", on=on
            )
        except Exception:
            return None
        left_part = self._join_device(
            df1, df2, "left_outer", on, exchange_staged=exchange_staged, tune=tune
        )
        if left_part is None:
            return None
        right_only = self._join_device(
            df2, df1, "anti", on, exchange_staged=exchange_staged, tune=tune
        )
        if right_only is None:
            return None
        ext = self._null_extend(right_only, out_schema, self.to_df(df1))
        if ext is None:
            return None
        lp = (
            left_part
            if list(left_part.schema.names) == out_schema.names
            else left_part[out_schema.names]  # type: ignore[index]
        )
        res = self.union(lp, ext, distinct=False)
        return res if isinstance(res, JaxDataFrame) else None

    def _null_extend(
        self, jr: DataFrame, out_schema: Schema, j1: JaxDataFrame
    ) -> Optional[JaxDataFrame]:
        """Extend right-only rows to the full join schema: absent (left-
        side) columns become NULL in each dtype's device representation."""
        import jax

        jr = self.to_df(jr)
        if not isinstance(jr, JaxDataFrame) or jr.host_table is not None:
            return None
        n = next(iter(jr.device_cols.values())).shape[0]
        sharding = row_sharding(self._mesh)
        cols: Dict[str, Any] = {}
        encodings: Dict[str, Any] = dict(jr.encodings)
        null_masks: Dict[str, Any] = dict(jr.null_masks)
        nan_new: set = set()
        for f in out_schema.fields:
            name = f.name
            if name in jr.device_cols:
                cols[name] = jr.device_cols[name]
                continue
            if name not in j1.device_cols:
                return None  # left column wasn't device-resident
            enc = j1.encodings.get(name)
            dt = np.dtype(j1.device_cols[name].dtype)
            if enc is not None and enc["kind"] == "dict":
                cols[name] = jax.device_put(
                    np.full(n, -1, dtype=dt), sharding
                )
                encodings[name] = dict(enc)
            elif enc is not None and enc["kind"] == "datetime":
                cols[name] = jax.device_put(np.zeros(n, dtype=dt), sharding)
                encodings[name] = dict(enc)
                null_masks[name] = jax.device_put(
                    np.ones(n, dtype=bool), sharding
                )
            elif np.issubdtype(dt, np.floating):
                cols[name] = jax.device_put(
                    np.full(n, np.nan, dtype=dt), sharding
                )
                nan_new.add(name)
            else:
                cols[name] = jax.device_put(np.zeros(n, dtype=dt), sharding)
                null_masks[name] = jax.device_put(
                    np.ones(n, dtype=bool), sharding
                )
        nan_cols = (
            None if jr._nan_cols is None else set(jr._nan_cols) | nan_new
        )
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols={name: cols[name] for name in out_schema.names},
                host_tbl=None,
                row_count=jr._row_count,
                valid_mask=jr.valid_mask,
                nan_cols=nan_cols,
                encodings=encodings,
                null_masks=null_masks,
                schema=out_schema,
            ),
        )

    def _cross_device(self, df1, df2) -> Optional[DataFrame]:
        """Cross join via the expansion kernel over a constant synthetic
        key (every left row matches every right row)."""
        import jax

        from ..ops.join import device_expand_join
        from ..shuffle.strategy import broadcast_max_rows

        j1, j2 = self.to_df(df1), self.to_df(df2)
        if not (
            isinstance(j1, JaxDataFrame)
            and isinstance(j2, JaxDataFrame)
            and j1.host_table is None
            and j2.host_table is None
            and len(j1.device_cols) > 0
            and len(j2.device_cols) > 0
        ):
            return None
        n_right = next(iter(j2.device_cols.values())).shape[0]
        if n_right > broadcast_max_rows(self.conf):
            return None
        if any(c in j1.schema for c in j2.schema.names):
            return None  # overlapping names — host handles the error
        mp = _safe_prefix("__mask__", j1.schema.names, j2.schema.names)
        lmp = _safe_prefix("__lmask__", j1.schema.names)
        kp = _safe_prefix("__xkey", j1.schema.names, j2.schema.names)
        rep = replicated_sharding(self._mesh)
        ones_l = jax.device_put(
            np.zeros(next(iter(j1.device_cols.values())).shape[0], np.int8),
            row_sharding(self._mesh),
        )
        ones_r = jax.device_put(np.zeros(n_right, np.int8), rep)
        left_cols = dict(j1.device_cols)
        for c, m in j1.null_masks.items():
            left_cols[f"{lmp}{c}"] = m
        left_cols[f"{kp}0"] = ones_l
        right_entries: List[Any] = []
        encodings: Dict[str, Any] = dict(j1.encodings)
        for v in j2.schema.names:
            arr = jax.device_put(j2.device_cols[v], rep)
            right_entries.append((v, arr, 0))
            enc = j2.encodings.get(v)
            if enc is not None:
                encodings[v] = enc
        for v, m in j2.null_masks.items():
            right_entries.append(
                (f"{mp}{v}", jax.device_put(m, rep), True)
            )
        res = device_expand_join(
            self._mesh,
            "inner",
            left_cols,
            j1.device_valid_mask(),
            [f"{kp}0"],
            [ones_r],
            jax.device_put(j2.device_valid_mask(), rep),
            right_entries,
            strategy="broadcast",
        )
        if res is None:
            return None
        new_cols, new_valid, _ = res
        null_masks: Dict[str, Any] = {}
        for c in list(j1.null_masks):
            m = new_cols.pop(f"{lmp}{c}", None)
            if m is not None:
                null_masks[c] = m
        for v in list(j2.null_masks):
            m = new_cols.pop(f"{mp}{v}", None)
            if m is not None:
                null_masks[v] = m
        new_cols.pop(f"{kp}0", None)
        out_schema = Schema(
            list(j1.schema.fields) + list(j2.schema.fields)
        )
        nan_cols = (
            None
            if j1._nan_cols is None or j2._nan_cols is None
            else set(j1._nan_cols) | set(j2._nan_cols)
        )
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols={n: new_cols[n] for n in out_schema.names},
                host_tbl=None,
                row_count=-1,
                valid_mask=new_valid,
                nan_cols=nan_cols,
                encodings={
                    k: v for k, v in encodings.items() if k in out_schema
                },
                null_masks=null_masks,
                schema=out_schema,
            ),
        )

    def _prepare_join_keys(
        self, j1: JaxDataFrame, j2: JaxDataFrame, keys: List[str]
    ) -> Optional[Any]:
        """Align the two frames' key representations for hashing/equality.

        Returns (left_key_arrs: Dict[mangled→arr], right_key_arrs: List) or
        None on fallback. Dictionary keys remap the right side's codes into
        the left's code space (host-side unification of the small
        dictionaries; NULLs get −1 left / −2 right so they never match);
        nullable numeric keys become float64 NaN views on both sides;
        epoch datetimes compare directly when the arrow types agree.
        """
        import jax
        import jax.numpy as jnp

        def _nullview(arr: Any, mask: Optional[Any]) -> Any:
            cache_key = ("nullview", self._mesh)
            if cache_key not in self._jit_cache:
                self._jit_cache[cache_key] = jax.jit(
                    lambda a, m: jnp.where(m, jnp.nan, a.astype(jnp.float64))
                )
            if mask is None:
                return arr.astype(jnp.float64)
            return self._jit_cache[cache_key](arr, mask)

        def _cast64(arr: Any, kind: str) -> Any:
            cache_key = ("joincast", kind, self._mesh)
            if cache_key not in self._jit_cache:
                tgt = jnp.float64 if kind == "f" else jnp.int64
                self._jit_cache[cache_key] = jax.jit(
                    lambda a, _t=tgt: a.astype(_t)
                )
            return self._jit_cache[cache_key](arr)

        kp = _safe_prefix("__key", j1.schema.names)
        left_keys: Dict[str, Any] = {}
        right_keys: List[Any] = []
        for i, k in enumerate(keys):
            lenc, renc = j1.encodings.get(k), j2.encodings.get(k)
            lm, rm = j1.null_masks.get(k), j2.null_masks.get(k)
            la, ra = j1.device_cols[k], j2.device_cols[k]
            if lenc is None and renc is None:
                if lm is None and rm is None:
                    lk, rk = la, ra
                    ld, rd = np.dtype(la.dtype), np.dtype(ra.dtype)
                    if ld != rd:
                        # cross-dtype keys match by VALUE via the common
                        # type (pandas/SQL coercion semantics — the host
                        # oracle does the same; int64 past 2^53 matches
                        # inexactly there too)
                        if (ld.kind == "u" and ld.itemsize == 8) or (
                            rd.kind == "u" and rd.itemsize == 8
                        ):
                            # uint64 ≥ 2^63 would wrap under an int64 cast
                            # into false matches — host fallback is exact
                            return None
                        if "f" in (ld.kind, rd.kind):
                            lk, rk = _cast64(la, "f"), _cast64(ra, "f")
                        elif ld.kind in "iub" and rd.kind in "iub":
                            lk, rk = _cast64(la, "i"), _cast64(ra, "i")
                        else:
                            return None
                elif np.dtype(la.dtype).kind == "f" or (
                    np.dtype(la.dtype).itemsize < 8
                    and np.dtype(ra.dtype).itemsize < 8
                ):
                    lk, rk = _nullview(la, lm), _nullview(ra, rm)
                else:
                    return None  # 64-bit ints with NULL keys lose exactness
            elif (
                lenc is not None
                and renc is not None
                and lenc["kind"] == "dict"
                and renc["kind"] == "dict"
            ):
                lk = la
                rk = self._remap_dict_codes(lenc, renc, ra)
            elif (
                lenc is not None
                and renc is not None
                and lenc["kind"] == "datetime"
                and renc["kind"] == "datetime"
                and lenc["type"] == renc["type"]
            ):
                if lm is not None or rm is not None:
                    return None  # masked epochs: 64-bit NULL-key problem
                lk, rk = la, ra
            else:
                return None
            left_keys[f"{kp}{i}__"] = lk
            right_keys.append(rk)
        return left_keys, right_keys

    def _remap_dict_codes(self, lenc: dict, renc: dict, right_codes: Any) -> Any:
        """Map right-side dictionary codes into the left's code space.

        Right values absent from the left dictionary get out-of-range codes
        (≥ len(left dict)) so they never match; NULL codes map −1 → −2 so
        NULL never equals NULL (SQL semantics)."""
        import jax
        import jax.numpy as jnp

        idx = pa.compute.index_in(
            renc["dictionary"], value_set=lenc["dictionary"]
        )
        n_left = len(lenc["dictionary"])
        mapped = idx.to_numpy(zero_copy_only=False)
        missing = np.isnan(mapped)
        mapped = np.where(
            missing, n_left + np.arange(len(mapped)), mapped
        ).astype(np.int32)
        table = jnp.asarray(mapped)

        cache_key = ("dictremap", self._mesh)
        if cache_key not in self._jit_cache:
            self._jit_cache[cache_key] = jax.jit(
                lambda codes, t: jnp.where(
                    codes < 0,
                    jnp.int32(-2),
                    t[jnp.clip(codes, 0, t.shape[0] - 1)],
                )
            )
        return self._jit_cache[cache_key](right_codes, table)

    def _join_device(
        self,
        df1,
        df2,
        kernel_how: str,
        on,
        exchange_staged: bool = False,
        tune=None,
    ) -> Optional[DataFrame]:
        """Try the device hash join; None → host fallback.

        ``exchange_staged=True`` is the device_exchange rung: broadcast
        is skipped (the right side already failed the per-device budget)
        and the co-partition step runs the staged one-hop exchange
        instead of the single-shot all-to-all."""
        from ..dataframe.utils import get_join_schemas
        from ..ops.join import device_hash_join

        if not (isinstance(df1, DataFrame) and isinstance(df2, DataFrame)):
            return None
        how_for_schema = {
            "inner": "inner",
            "left_outer": "left_outer",
            "semi": "left_semi",
            "anti": "left_anti",
        }[kernel_how]
        try:
            key_schema, out_schema = get_join_schemas(
                df1, df2, how=how_for_schema, on=on
            )
        except Exception:
            return None
        keys = key_schema.names
        # cheap schema pre-checks BEFORE any device conversion
        supported = all(
            pa.types.is_integer(t)
            or pa.types.is_floating(t)
            or pa.types.is_boolean(t)
            or pa.types.is_string(t)
            or pa.types.is_large_string(t)
            or pa.types.is_timestamp(t)
            or pa.types.is_date(t)
            for t in key_schema.types
        )
        if len(keys) == 0 or not supported:
            return None
        j1, j2 = self.to_df(df1), self.to_df(df2)
        if not (
            isinstance(j1, JaxDataFrame)
            and isinstance(j2, JaxDataFrame)
            and j2.host_table is None
            and len(j2.device_cols) == len(j2.schema)
            and all(k in j1.device_cols for k in keys)
        ):
            return None
        prepared = self._prepare_join_keys(j1, j2, keys)
        if prepared is None:
            return None
        left_key_arrs, right_key_arrs = prepared
        value_names = [
            n for n in j2.schema.names if n not in keys and n in out_schema
        ]
        # value entries: (out_name, array, left_outer miss fill); masked
        # columns ship their mask as an extra gathered array (miss = True)
        import math

        import jax

        mp = _safe_prefix("__mask__", j1.schema.names, j2.schema.names)
        lmp = _safe_prefix("__lmask__", j1.schema.names)
        right_entries: List[Any] = []
        out_value_encodings: Dict[str, Any] = {}
        gen_mask_names: List[str] = []  # plain non-floats: mask = ~match
        for v in value_names:
            arr = j2.device_cols[v]
            enc = j2.encodings.get(v)
            if enc is not None and enc["kind"] == "dict":
                right_entries.append((v, arr, -1))
                out_value_encodings[v] = enc
            elif np.issubdtype(np.dtype(arr.dtype), np.floating):
                right_entries.append((v, arr, math.nan))
            else:
                right_entries.append((v, arr, 0))
                if enc is not None:
                    out_value_encodings[v] = enc
                if kernel_how == "left_outer" and v not in j2.null_masks:
                    gen_mask_names.append(v)
            if v in j2.null_masks:
                right_entries.append(
                    (f"{mp}{v}", j2.null_masks[v], True)
                )
        from ..shuffle.strategy import broadcast_max_rows

        n_right = next(iter(j2.device_cols.values())).shape[0]
        encodings: Dict[str, Any] = {}
        null_masks: Dict[str, Any] = {}
        if not exchange_staged and n_right <= broadcast_max_rows(self.conf):
            strategy = "broadcast"
            self._last_join_strategy = "broadcast"
            rep = replicated_sharding(self._mesh)
            right_entries = [
                (n, jax.device_put(a, rep), f) for n, a, f in right_entries
            ]
            right_key_arrs = [
                jax.device_put(a, rep) for a in right_key_arrs
            ]
            right_valid = jax.device_put(j2.device_valid_mask(), rep)
            left_cols = dict(j1.device_cols)
            left_cols.update(left_key_arrs)
            left_valid = j1.device_valid_mask()
            host_tbl = j1.host_table  # rows stay in place → stays aligned
            nan_cols = j1._nan_cols
            encodings = dict(j1.encodings)  # non-key left cols ride along
            null_masks = dict(j1.null_masks)
        else:
            strategy = "shuffle"
            self._last_join_strategy = (
                "device_exchange" if exchange_staged else "copartition"
            )
            if j1.host_table is not None:
                return None  # rows move; host columns can't follow
            left_cols = dict(j1.device_cols)
            # left null masks travel with their rows through the exchange
            for c, m in j1.null_masks.items():
                left_cols[f"{lmp}{c}"] = m
            left_cols.update(left_key_arrs)
            left_valid = j1.device_valid_mask()
            right_valid = j2.device_valid_mask()
            host_tbl = None
            nan_cols = None
            encodings = dict(j1.encodings)
        if strategy == "shuffle":
            # ONE exchange, shared by the unique probe and any dup-key
            # expansion retry (the retry must not repeat the all-to-all)
            if exchange_staged:
                from ..obs import get_tracer
                from ..shuffle.exchange import staged_copartition_by_keys
                from ..shuffle.strategy import exchange_stage_bytes

                stage_bytes = exchange_stage_bytes(self.conf)
                stages_before = self._shuffle_stats.get("device_exchange_stages")
                with get_tracer().span(
                    "shuffle.exchange", cat="shuffle", annotate=True
                ) as xsp:
                    (
                        left_cols,
                        left_valid,
                        right_key_arrs,
                        right_entries,
                        right_valid,
                    ) = staged_copartition_by_keys(
                        self._mesh,
                        left_cols,
                        left_valid,
                        list(left_key_arrs.keys()),
                        right_key_arrs,
                        right_entries,
                        right_valid,
                        stage_bytes,
                        stats=self._shuffle_stats,
                    )
                    xsp.set(
                        stage_bytes=stage_bytes,
                        peak_stage_bytes=self._shuffle_stats.get(
                            "device_exchange_peak_stage_bytes"
                        ),
                    )
                if tune is not None:
                    tune.observe_exchange(
                        stages=self._shuffle_stats.get("device_exchange_stages")
                        - stages_before,
                        peak_stage_bytes=self._shuffle_stats.get(
                            "device_exchange_peak_stage_bytes"
                        ),
                    )
            else:
                from ..ops.join import copartition_by_keys

                (
                    left_cols,
                    left_valid,
                    right_key_arrs,
                    right_entries,
                    right_valid,
                ) = copartition_by_keys(
                    self._mesh,
                    left_cols,
                    left_valid,
                    list(left_key_arrs.keys()),
                    right_key_arrs,
                    right_entries,
                    right_valid,
                )
            strategy = "local"
        res = device_hash_join(
            self._mesh,
            kernel_how,
            left_cols,
            left_valid,
            list(left_key_arrs.keys()),
            right_key_arrs,
            right_valid,
            right_entries,
            strategy=strategy,
        )
        expanded = False
        if res is None:
            # duplicate right keys: the 1:N/N:M expansion path. semi/anti
            # keep row alignment (mask-only); inner/left_outer materialize
            # (left row, match) pairs — rows move, host columns can't follow
            from ..ops.join import device_expand_join

            if kernel_how in ("inner", "left_outer"):
                if j1.host_table is not None:
                    return None
                if strategy == "broadcast":
                    # the unique-path broadcast payload omitted the left
                    # masks (rows didn't move); expansion gathers rows, so
                    # masks must ride along
                    for c, m2 in j1.null_masks.items():
                        left_cols[f"{lmp}{c}"] = m2
                    host_tbl = None
                    null_masks = {}
            res = device_expand_join(
                self._mesh,
                kernel_how,
                left_cols,
                left_valid,
                list(left_key_arrs.keys()),
                right_key_arrs,
                right_valid,
                right_entries,
                strategy=strategy,
            )
            if res is None:
                return None
            expanded = True
        new_cols, new_valid, match = res
        # reassemble: pop probe keys, split off mask arrays
        for mk in left_key_arrs:
            new_cols.pop(mk, None)
        if strategy == "local" or expanded:
            for c in list(j1.null_masks):
                m = new_cols.pop(f"{lmp}{c}", None)
                if m is not None:
                    null_masks[c] = m
        for v in value_names:
            m = new_cols.pop(f"{mp}{v}", None)
            if m is not None:
                null_masks[v] = m
        if kernel_how == "left_outer":
            if nan_cols is not None:
                # gathered float values may be NaN-filled on misses
                nan_cols = set(nan_cols) | {
                    v
                    for v in value_names
                    if np.issubdtype(
                        np.dtype(j2.device_cols[v].dtype), np.floating
                    )
                }
            if len(gen_mask_names) > 0:
                import jax.numpy as jnp

                cache_key = ("notmask", self._mesh)
                if cache_key not in self._jit_cache:
                    import jax as _jax

                    self._jit_cache[cache_key] = _jax.jit(jnp.logical_not)
                miss = self._jit_cache[cache_key](match)
                for v in gen_mask_names:
                    null_masks[v] = miss
        encodings.update(out_value_encodings)
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols={
                    n: new_cols[n] for n in out_schema.names if n in new_cols
                },
                host_tbl=host_tbl,
                row_count=-1,
                valid_mask=new_valid,
                nan_cols=nan_cols,
                encodings={
                    k: v
                    for k, v in encodings.items()
                    if k in out_schema
                },
                null_masks={
                    k: v for k, v in null_masks.items() if k in out_schema
                },
                schema=out_schema,
            ),
        )

    # ---- co-sharded zip/comap ---------------------------------------------
    def zip(
        self,
        dfs: DataFrames,
        how: str = "inner",
        partition_spec: Optional[PartitionSpec] = None,
        temp_path: Optional[str] = None,
        to_file_threshold: int = -1,
    ) -> DataFrame:
        """Device zip: hash-co-partition every input by the zip keys with
        the all-to-all exchange — no arrow-IPC blobs (SURVEY §5.8 redesign
        of the reference's serialize-by-partition protocol). Falls back to
        the host blob protocol for cross zips, host-resident frames, and
        keys whose device form isn't comparable across frames (strings /
        nullable / NaN-able keys)."""
        from ..collections.partition import PartitionSpec as _PSpec
        from .streaming import is_stream_frame, streaming_zip
        from .zipped import ZippedJaxDataFrame

        spec = partition_spec if partition_spec is not None else _PSpec()
        if any(is_stream_frame(d) for d in dfs.values()):
            # key-sorted one-pass inputs: defer to the co-batched
            # sorted-merge comap (bounded memory); ineligible shapes
            # (cross / keyless) materialize below
            zs = streaming_zip(self, dfs, how, spec)
            if zs is not None:
                return zs
        keys = list(spec.partition_by)
        if how.lower() != "cross" and len(keys) == 0 and len(dfs) > 0:
            keys = [
                n
                for n in dfs[0].schema.names
                if all(n in d.schema for d in dfs.values())
            ]
        if how.lower() != "cross" and len(keys) > 0:
            jdfs = [self.to_df(d) for d in dfs.values()]

            def _key_ok(j: JaxDataFrame, k: str) -> bool:
                if k not in j.device_cols:
                    return False
                enc = j.encodings.get(k)
                if enc is not None and enc["kind"] == "dict":
                    return True  # co-located via code remapping below
                # NULL/NaN keys don't group across frames on the host side
                # (NaN/NaT break the key-tuple lookup) → blob protocol
                return (
                    enc is None
                    and k not in j.null_masks
                    and not j.maybe_nan(k)
                )

            device_ok = all(
                isinstance(j, JaxDataFrame)
                and j.host_table is None
                and len(j.device_cols) == len(j.schema)
                and all(_key_ok(j, k) for k in keys)
                for j in jdfs
            )
            if device_ok:
                # union dictionary per string key: every frame's codes remap
                # into ONE shared space so equal values co-locate even when
                # absent from other frames' dictionaries
                union_dicts: Dict[str, Any] = {}
                for k in keys:
                    dicts = [
                        j.encodings[k]["dictionary"]
                        for j in jdfs
                        if j.encodings.get(k, {}).get("kind") == "dict"
                    ]
                    if len(dicts) > 0:
                        union_dicts[k] = pa.concat_arrays(dicts).unique()
                co = [
                    self._zip_repartition(j, union_dicts, keys) for j in jdfs
                ]
                return ZippedJaxDataFrame(
                    frames=co,  # type: ignore[arg-type]
                    names=list(dfs.keys()),
                    named=dfs.has_key,
                    how=how.lower(),
                    keys=keys,
                    schemas=[j.schema for j in jdfs],
                    mesh=self._mesh,
                    presort=dict(spec.presort),
                )
        return super().zip(
            dfs,
            how=how,
            partition_spec=partition_spec,
            temp_path=temp_path,
            to_file_threshold=to_file_threshold,
        )

    def _zip_repartition(
        self, j: JaxDataFrame, union_dicts: Dict[str, Any], keys: List[str]
    ) -> JaxDataFrame:
        """Hash-repartition a zip input so equal key VALUES co-locate across
        frames: dictionary keys hash via codes remapped into the shared
        union-dictionary space (NULL codes stay −1, so every frame's NULL
        rows share a shard and form one comap group)."""
        from ..collections.partition import PartitionSpec as _PSpec
        from ..ops.shuffle import compute_dest, exchange_rows

        dict_keys = [k for k in keys if k in union_dicts]
        if len(dict_keys) == 0:
            return self.repartition(j, _PSpec(algo="hash", by=keys))  # type: ignore[return-value]
        import jax
        import jax.numpy as jnp

        key_arrs = []
        for k in keys:
            arr = j.device_cols[k]
            if k in dict_keys:
                mapped = np.asarray(
                    pa.compute.index_in(
                        j.encodings[k]["dictionary"], value_set=union_dicts[k]
                    ).to_numpy(zero_copy_only=False)
                )
                if mapped.size == 0:  # no dictionary entries → all NULL rows
                    mapped = np.asarray([-1])
                table = jnp.asarray(mapped.astype(np.int32))
                ck = ("zipremap", self._mesh)
                if ck not in self._jit_cache:
                    self._jit_cache[ck] = jax.jit(
                        lambda c, t: jnp.where(
                            c < 0,
                            jnp.int32(-1),  # NULLs co-locate across frames
                            t[jnp.clip(c, 0, t.shape[0] - 1)],
                        )
                    )
                arr = self._jit_cache[ck](arr, table)
            key_arrs.append(arr)
        valid = j.device_valid_mask()
        dest = compute_dest(self._mesh, "hash", key_arrs, valid)
        mp = _safe_prefix("__mask__", j.schema.names)
        payload = dict(j.device_cols)
        for c, m in j.null_masks.items():
            payload[f"{mp}{c}"] = m
        new_payload, new_valid, _ = exchange_rows(
            self._mesh, payload, valid, dest
        )
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols={c: new_payload[c] for c in j.device_cols},
                host_tbl=None,
                row_count=j.count(),
                valid_mask=new_valid,
                nan_cols=j._nan_cols,
                encodings=dict(j.encodings),
                null_masks={
                    c: new_payload[f"{mp}{c}"] for c in j.null_masks
                },
                schema=j.schema,
            ),
        )

    def comap(
        self,
        df: DataFrame,
        map_func: Callable,
        output_schema: Any,
        partition_spec: Optional[PartitionSpec] = None,
        on_init: Optional[Callable] = None,
    ) -> DataFrame:
        """Comap over a device-zipped frame: each co-sharded frame transfers
        to host once (shard-local on multi-host meshes — the exchange
        already placed each key's rows on its owner), groups by the zip
        keys, and the cotransform runs per key group. No blob rows are
        ever built or parsed."""
        from ..collections.partition import PartitionSpec as _PSpec
        from ..dataframe import ArrayDataFrame
        from .streaming import ZippedStreamDataFrame, streaming_comap
        from .zipped import ZippedJaxDataFrame

        if isinstance(df, ZippedStreamDataFrame):
            return streaming_comap(
                self, df, map_func, output_schema,
                partition_spec=partition_spec, on_init=on_init,
            )
        if not isinstance(df, ZippedJaxDataFrame):
            return super().comap(
                df,
                map_func,
                output_schema,
                partition_spec=partition_spec,
                on_init=on_init,
            )
        out_schema = (
            output_schema
            if isinstance(output_schema, Schema)
            else Schema(output_schema)
        )
        keys = df._zip_keys
        how = df._zip_how
        schemas = df._zip_schemas
        names = [
            df._zip_names[i] if df._zip_named else f"_{i}"
            for i in range(len(schemas))
        ]
        spec = _PSpec(partition_spec, by=keys) if partition_spec is not None else _PSpec(by=keys)
        cursor = spec.get_cursor(df.schema, 0)
        if on_init is not None:
            on_init(
                0,
                DataFrames(
                    {n: ArrayDataFrame([], s) for n, s in zip(names, schemas)}
                ),
            )
        presort = dict(getattr(df, "_zip_presort", {}) or {})
        # the comap-time spec's presort (e.g. from the cotransformer's own
        # partition settings) overrides the zip-time one, matching the host
        # blob protocol where serialization uses the effective spec
        if len(spec.presort) > 0:
            presort = dict(spec.presort)
        # multi-host: the zip exchange already placed each key's rows on
        # exactly one shard, so every process transfers ONLY its local
        # shards and runs the cotransform for its own keys — the per-host
        # parallel execution the reference gets from cluster executors
        from ..parallel.distributed import is_multihost

        multihost = is_multihost()
        if multihost:
            frames_pd = [f.as_pandas_local() for f in df.zip_frames]
        else:
            frames_pd = [f.as_pandas() for f in df.zip_frames]
        if len(presort) > 0:
            # na_position="first" matches the host blob protocol's partition
            # presort (PandasMapEngine) so NULL rows order identically
            frames_pd = [
                p.sort_values(
                    by=[c for c in presort if c in p.columns],
                    ascending=[v for c, v in presort.items() if c in p.columns],
                    kind="mergesort",
                    na_position="first",
                )
                if len(p) > 0 and any(c in p.columns for c in presort)
                else p
                for p in frames_pd
            ]
        grouped: List[Dict[Any, pd.DataFrame]] = []
        key_order: List[Any] = []
        seen: set = set()
        for p in frames_pd:
            g: Dict[Any, pd.DataFrame] = {}
            if len(p) > 0:
                for kv, sub in p.groupby(keys, dropna=False, sort=False):
                    kt = _null_safe_key(kv)
                    g[kt] = sub
                    if kt not in seen:
                        seen.add(kt)
                        key_order.append(kt)
            grouped.append(g)
        results: List[pa.Table] = []
        no = 0
        for kt in key_order:
            subs = [g.get(kt) for g in grouped]
            if how == "inner" and any(s is None for s in subs):
                continue
            if how == "left_outer" and subs[0] is None:
                continue
            if how == "right_outer" and subs[-1] is None:
                continue
            dfs_obj = DataFrames(
                {
                    n: (
                        PandasDataFrame(
                            s.reset_index(drop=True), sch, pandas_df_wrapper=True
                        )
                        if s is not None
                        else ArrayDataFrame([], sch)
                    )
                    for n, s, sch in zip(names, subs, schemas)
                }
            )
            row = list(kt) + [None] * len(schemas)
            cursor.set(lambda r=row: r, no, 0)
            no += 1
            out = map_func(cursor, dfs_obj)
            results.append(out.as_local_bounded().as_arrow())
        if multihost:
            tbl = (
                pa.concat_tables(
                    [t.cast(out_schema.pa_schema) for t in results]
                )
                if len(results) > 0
                else out_schema.create_empty_arrow_table()
            )
            return self._from_process_local_table(tbl)
        if len(results) == 0:
            return self.to_df(ArrayDataFrame([], out_schema))
        tbl = pa.concat_tables(
            [t.cast(out_schema.pa_schema) for t in results]
        )
        return self.to_df(ArrowDataFrame(tbl))

    def _from_process_local_table(self, tbl: pa.Table) -> JaxDataFrame:
        """Assemble a global JaxDataFrame from per-process row sets.

        Each process contributes its own rows (counts may differ); per-shard
        capacity is negotiated with an allgather of the local counts so all
        processes agree on ONE padded global shape, then the device array is
        built from process-local data — no host ever sees another host's
        rows. String columns get a cross-process dictionary union: local
        dictionaries allgather (arrow IPC over padded byte buffers), every
        process derives the SAME sorted union dictionary, and local codes
        remap into it. Datetime encodings are schema-derived and identical
        everywhere, so they pass straight through.
        """
        import jax
        from jax.experimental import multihost_utils

        from .dataframe import encode_arrow_for_device

        np_cols, host_tbl, meta = encode_arrow_for_device(tbl, encode=True)
        assert_or_throw(
            host_tbl is None,
            FugueInvalidOperation(
                "multi-host comap outputs support device-representable "
                "columns only (numeric/bool/string/datetime — no binary/"
                "nested)"
            ),
        )
        dict_encs = {
            n: e for n, e in meta["encodings"].items() if e.get("kind") == "dict"
        }  # datetime encodings are process-independent and pass through
        if len(dict_encs) > 0:
            unions = _allgather_dictionaries(
                {n: e["dictionary"] for n, e in dict_encs.items()}
            )
            for name, enc in dict_encs.items():
                gdict = unions[name].cast(enc["type"])
                # remap local codes into the union's (sorted) code space
                to_global = _dict_mapping(enc["dictionary"], gdict)
                codes = np_cols[name]
                np_cols[name] = np.where(
                    codes >= 0, to_global[np.clip(codes, 0, None)], -1
                ).astype(np.int32)
                meta["encodings"][name] = {
                    "kind": "dict",
                    "dictionary": gdict,
                    "type": enc["type"],
                    "sorted": True,
                }
        local_n = tbl.num_rows
        counts = np.asarray(
            multihost_utils.process_allgather(np.asarray([local_n]))
        ).reshape(-1)
        local_shards = jax.local_device_count()
        total_shards = num_row_shards(self._mesh)
        per_shard = max(
            1, int(-(-int(counts.max()) // local_shards))
        )  # ceil over the busiest process
        cap = 1 << (per_shard - 1).bit_length()  # pow2 keeps jit cache small
        local_rows = local_shards * cap
        global_rows = total_shards * cap
        sharding = row_sharding(self._mesh)

        def _pad(arr: np.ndarray, fill: Any) -> np.ndarray:
            out = np.full(local_rows, fill, dtype=arr.dtype)
            out[: len(arr)] = arr
            return out

        cols = {
            k: jax.make_array_from_process_local_data(
                sharding, _pad(v, 0), (global_rows,)
            )
            for k, v in np_cols.items()
        }
        valid = jax.make_array_from_process_local_data(
            sharding,
            _pad(np.ones(local_n, dtype=bool), False),
            (global_rows,),
        )
        # mask-key sets must be IDENTICAL on every process (divergent frame
        # structure → divergent jitted programs → collective deadlock):
        # allgather the local sets and union them, filling absentees with
        # all-False masks
        schema_names = [f.name for f in tbl.schema]
        local_has = np.asarray(
            [n in meta["null_masks"] for n in schema_names], dtype=np.int32
        )
        union_has = (
            np.asarray(multihost_utils.process_allgather(local_has))
            .reshape(-1, len(schema_names))
            .max(axis=0)
        )
        null_masks = {}
        for i, n in enumerate(schema_names):
            if union_has[i]:
                m = meta["null_masks"].get(
                    n, np.zeros(local_n, dtype=bool)
                )
                null_masks[n] = jax.make_array_from_process_local_data(
                    sharding, _pad(m, True), (global_rows,)
                )
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols=cols,
                host_tbl=None,
                row_count=int(counts.sum()),
                valid_mask=valid,
                # nan_cols derived from LOCAL rows would diverge between
                # processes (different plan gating → collective deadlock);
                # None = conservatively maybe-NaN everywhere, identically
                nan_cols=None,
                # dict encodings hold the UNION dictionary (identical on
                # every process); datetime encodings are schema-derived
                encodings=meta["encodings"],
                null_masks=null_masks,
                schema=Schema(tbl.schema),
            ),
        )

    @traced_verb("engine.union")
    def union(self, df1, df2, distinct: bool = True) -> DataFrame:
        """Device union: per-shard concatenation of both frames' blocks in
        one ``shard_map``. Dictionary columns unify into one (re-sorted)
        union dictionary with both sides' codes remapped; null masks
        concatenate with their columns; epoch datetimes concatenate when
        the arrow types agree. ``distinct=True`` runs the device distinct
        on the result."""
        j1, j2 = self.to_df(df1), self.to_df(df2)
        compatible = (
            isinstance(j1, JaxDataFrame)
            and isinstance(j2, JaxDataFrame)
            and j1.schema == j2.schema
            and j1.host_table is None
            and j2.host_table is None
            and len(j1.device_cols) > 0
            and all(
                j1.device_cols[c].dtype == j2.device_cols[c].dtype
                for c in j1.schema.names
            )
            # per-column encodings must agree in KIND (schema equality
            # already forces matching arrow types, incl. timestamp units)
            and all(
                j1.encodings.get(c, {}).get("kind")
                == j2.encodings.get(c, {}).get("kind")
                for c in j1.schema.names
            )
        )
        if compatible:
            import jax
            import jax.numpy as jnp
            from jax.sharding import PartitionSpec as JP

            mesh = self._mesh
            # unify dictionary columns: sorted union dictionary + remapped
            # codes on both sides (NULL code −1 is preserved by the remap)
            cols1, cols2 = dict(j1.device_cols), dict(j2.device_cols)
            encodings: Dict[str, Any] = {}
            for c in j1.schema.names:
                enc1, enc2 = j1.encodings.get(c), j2.encodings.get(c)
                if enc1 is None:
                    continue
                if enc1["kind"] == "datetime":
                    encodings[c] = enc1
                    continue
                union_dict = _sorted_union_dictionary(
                    [enc1["dictionary"], enc2["dictionary"]]
                )
                ck = ("zipremap", mesh)
                if ck not in self._jit_cache:
                    self._jit_cache[ck] = jax.jit(
                        lambda cd, t: jnp.where(
                            cd < 0,
                            jnp.int32(-1),
                            t[jnp.clip(cd, 0, t.shape[0] - 1)],
                        )
                    )
                for cols, enc in ((cols1, enc1), (cols2, enc2)):
                    mapped = _dict_mapping(enc["dictionary"], union_dict)
                    cols[c] = self._jit_cache[ck](
                        cols[c], jnp.asarray(mapped)
                    )
                encodings[c] = {
                    "kind": "dict",
                    "dictionary": union_dict,
                    "type": enc1["type"],
                    "sorted": True,
                }
            # null masks travel with their columns through the concat; a
            # side without a mask for the column contributes all-False
            mp = _safe_prefix("__mask__", j1.schema.names)
            vp = _safe_prefix("__valid__", cols1.keys())
            for c in set(j1.null_masks) | set(j2.null_masks):
                cols1[f"{mp}{c}"] = j1.null_masks.get(
                    c, self._false_mask_like(j1)
                )
                cols2[f"{mp}{c}"] = j2.null_masks.get(
                    c, self._false_mask_like(j2)
                )
            mask_names = [n for n in cols1 if n.startswith(mp)]
            cache_key = (
                "union",
                mesh,
                tuple(sorted(cols1)),
                tuple(str(cols1[c].dtype) for c in sorted(cols1)),
                next(iter(cols1.values())).shape[0],
                next(iter(cols2.values())).shape[0],
            )
            if cache_key not in self._jit_cache:

                def compute(c1: Dict[str, Any], v1: Any, c2: Dict[str, Any], v2: Any):
                    def shard_fn(a: Dict[str, Any], va: Any, b: Dict[str, Any], vb: Any):
                        out = {
                            n: jnp.concatenate([a[n], b[n]]) for n in a
                        }
                        out[vp] = jnp.concatenate([va, vb])
                        return out

                    return shard_map(
                        shard_fn,
                        mesh=mesh,
                        in_specs=(JP(ROW_AXIS),) * 4,
                        out_specs=JP(ROW_AXIS),
                    )(c1, v1, c2, v2)

                self._jit_cache[cache_key] = jax.jit(compute)
            out = self._jit_cache[cache_key](
                cols1,
                j1.device_valid_mask(),
                cols2,
                j2.device_valid_mask(),
            )
            valid = out.pop(vp)
            null_masks = {
                n[len(mp):]: out.pop(n) for n in mask_names
            }
            res: DataFrame = JaxDataFrame(
                mesh=mesh,
                _internal=dict(
                    device_cols=out,
                    host_tbl=None,
                    row_count=-1,
                    valid_mask=valid,
                    nan_cols=(
                        None
                        if j1._nan_cols is None or j2._nan_cols is None
                        else j1._nan_cols | j2._nan_cols
                    ),
                    encodings=encodings,
                    null_masks=null_masks,
                    schema=j1.schema,
                ),
            )
            return self.distinct(res) if distinct else res
        with _host_op("union", "setop_not_lowered") as sp:
            return self._back(
                self._host_engine.union(self._host(df1, sp), self._host(df2, sp), distinct=distinct)
            )

    def _false_mask_like(self, jdf: JaxDataFrame) -> Any:
        """An all-False device bool array row-aligned with the frame."""
        import jax
        import jax.numpy as jnp

        ck = ("falsemask", self._mesh)
        if ck not in self._jit_cache:
            self._jit_cache[ck] = jax.jit(
                lambda t: jnp.zeros(t.shape[0], dtype=bool),
                out_shardings=row_sharding(self._mesh),
            )
        return self._jit_cache[ck](next(iter(jdf.device_cols.values())))

    def _setop_device_ok(self, df: Any) -> bool:
        """Set-difference semantics treat NULL = NULL; the join kernels
        treat NULL keys as never-matching — so the device path requires
        provably NULL-free plain frames."""
        j = self.to_df(df)
        return (
            isinstance(j, JaxDataFrame)
            and j.host_table is None
            and not j.has_encoded
            and j._nan_cols is not None
            and len(j._nan_cols) == 0
            and len(j.device_cols) > 0
        )

    @traced_verb("engine.subtract")
    def subtract(self, df1, df2, distinct: bool = True) -> DataFrame:
        """``distinct=True`` lowers to a device ANTI join of the two
        distinct frames on ALL columns (the deduped right side satisfies
        the unique-key requirement)."""
        if distinct and self._setop_device_ok(df1) and self._setop_device_ok(df2):
            d1, d2 = self.distinct(df1), self.distinct(df2)
            res = self._join_device(
                d1, d2, "anti", on=list(self.to_df(df1).schema.names)
            )
            if res is not None:
                return res
        with _host_op("subtract", "setop_not_lowered") as sp:
            return self._back(
                self._host_engine.subtract(self._host(df1, sp), self._host(df2, sp), distinct=distinct)
            )

    @traced_verb("engine.intersect")
    def intersect(self, df1, df2, distinct: bool = True) -> DataFrame:
        """``distinct=True`` lowers to a device SEMI join of the two
        distinct frames on ALL columns."""
        if distinct and self._setop_device_ok(df1) and self._setop_device_ok(df2):
            d1, d2 = self.distinct(df1), self.distinct(df2)
            res = self._join_device(
                d1, d2, "semi", on=list(self.to_df(df1).schema.names)
            )
            if res is not None:
                return res
        with _host_op("intersect", "setop_not_lowered") as sp:
            return self._back(
                self._host_engine.intersect(self._host(df1, sp), self._host(df2, sp), distinct=distinct)
            )

    def _group_key_cols(self, jdf: JaxDataFrame, names: List[str]) -> Any:
        """(key_cols_for_kernel, mask_col_names) — nullable columns add
        their null mask as an extra key so NULL forms its own group distinct
        from the fill value. Maybe-NaN float keys canonicalize to (0, isnan)
        the same way: NaN != NaN would otherwise split every NULL key into
        its own group, diverging from the oracle's dropna=False grouping."""
        key_cols: Dict[str, Any] = {}
        mask_names: Dict[str, str] = {}

        def _mangled(c: str) -> str:
            mn = f"__null__{c}"
            while mn in jdf.schema:
                mn = "_" + mn
            return mn

        for c in names:
            arr = jdf.device_cols[c]
            if c in jdf.null_masks:
                key_cols[c] = arr
                mn = _mangled(c)
                key_cols[mn] = jdf.null_masks[c]
                mask_names[c] = mn
            elif np.issubdtype(np.dtype(arr.dtype), np.floating) and jdf.maybe_nan(c):
                import jax
                import jax.numpy as jnp

                ck = ("nankey", self._mesh)
                if ck not in self._jit_cache:
                    self._jit_cache[ck] = jax.jit(
                        lambda a: (
                            jnp.where(jnp.isnan(a), jnp.zeros_like(a), a),
                            jnp.isnan(a),
                        )
                    )
                canon, isnan = self._jit_cache[ck](arr)
                key_cols[c] = canon
                mn = _mangled(c)
                key_cols[mn] = isnan
                mask_names[c] = mn
            else:
                key_cols[c] = arr
        return key_cols, mask_names

    def _decode_partial_keys(
        self, jdf: JaxDataFrame, partials: pd.DataFrame, mask_names: Dict[str, str]
    ) -> pd.DataFrame:
        """Restore original key semantics on host partials: dictionary codes
        → values, epoch ints → timestamps, masked cells → NA."""
        res = partials
        for c, mn in mask_names.items():
            res[c] = res[c].mask(res[mn].astype(bool))
            res = res.drop(columns=[mn])
        for c, enc in jdf.encodings.items():
            if c not in res.columns:
                continue
            if enc["kind"] == "dict":
                codes = res[c].to_numpy()
                valid = codes >= 0
                decoded = enc["dictionary"].take(
                    pa.array(
                        np.where(valid, codes, 0).astype(np.int64), mask=~valid
                    )
                )
                res[c] = decoded.to_pandas()
            elif enc["kind"] == "datetime":
                ints = res[c]
                na = ints.isna()
                # the epoch's storage width: arrow casts date32 from int32 only
                storage = np.int32 if pa.types.is_date32(enc["type"]) else np.int64
                arr = pa.array(
                    ints.fillna(0).to_numpy().astype(storage),
                    mask=na.to_numpy() if na.any() else None,
                ).cast(enc["type"])
                res[c] = arr.to_pandas()
        return res

    @traced_verb("engine.distinct")
    def distinct(self, df: DataFrame) -> DataFrame:
        """Device distinct when every column is device-resident: the groupby
        kernel with a presence count — keys of the merged partials are the
        distinct rows. Dictionary codes / epoch ints / null masks group by
        their device identity and decode on the O(groups) host result.
        One-pass streams dedupe chunk-wise without materializing."""
        from .streaming import is_stream_frame, streaming_distinct

        if is_stream_frame(df):
            return streaming_distinct(self, df)
        from ..ops.segment import device_groupby_partials

        from ..constants import FUGUE_TPU_CONF_MAX_PARTIAL_ROWS
        from ..ops.segment import PartialsTooLarge

        jdf = self.to_df(df)
        if (
            isinstance(jdf, JaxDataFrame)
            and jdf.host_table is None
            and len(jdf.device_cols) > 0
            and len(jdf.device_cols) == len(jdf.schema)
        ):
            key_cols, mask_names = self._group_key_cols(jdf, jdf.schema.names)
            first = next(iter(key_cols))
            count_name = "__n__"
            while count_name in jdf.schema:  # never shadow a user column
                count_name = "_" + count_name
            try:
                partials = device_groupby_partials(
                    self._mesh,
                    key_cols,
                    [(count_name, "count", key_cols[first])],
                    jdf.device_valid_mask(),
                    max_partial_rows=self.conf.get(
                        FUGUE_TPU_CONF_MAX_PARTIAL_ROWS, 1 << 22
                    ),
                )
            except PartialsTooLarge:
                # near-unique rows: the O(groups) transfer stops paying off
                with _host_op("distinct", "partials_too_large") as sp:
                    return self._back(self._host_engine.distinct(self._host(df, sp)))
            res = partials.drop(columns=[count_name]).drop_duplicates(
                ignore_index=True
            )
            res = self._decode_partial_keys(jdf, res, mask_names)
            return self.to_df(PandasDataFrame(res[jdf.schema.names], jdf.schema))
        with _host_op("distinct", "host_frame") as sp:
            return self._back(self._host_engine.distinct(self._host(df, sp)))

    @traced_verb("engine.dropna")
    def dropna(self, df, how="any", thresh=None, subset=None) -> DataFrame:
        """All-device frames: NULL = NaN float, null-masked cell, or
        negative dictionary code — drop by extending the validity mask,
        zero data movement."""
        jdf = self.to_df(df)
        if (
            isinstance(jdf, JaxDataFrame)
            and jdf.host_table is None
            and len(jdf.device_cols) == len(jdf.schema)
        ):
            import jax
            import jax.numpy as jnp

            cols = subset or jdf.schema.names
            dict_cols = frozenset(
                c for c, e in jdf.encodings.items() if e["kind"] == "dict"
            )
            key = (
                "dropna",
                tuple(cols),
                how,
                thresh,
                tuple(jdf.schema.names),
                dict_cols,
                frozenset(jdf.null_masks),
            )
            if key not in self._jit_cache:

                def compute(
                    dcols: Dict[str, Any], masks: Dict[str, Any], valid: Any
                ) -> Any:
                    notnull = []
                    for c in cols:
                        nn = jnp.ones_like(valid)
                        if jnp.issubdtype(dcols[c].dtype, jnp.floating):
                            nn = nn & ~jnp.isnan(dcols[c])
                        if c in masks:
                            nn = nn & ~masks[c]
                        if c in dict_cols:
                            nn = nn & (dcols[c] >= 0)
                        notnull.append(nn)
                    stacked = jnp.stack(notnull, axis=0)
                    if thresh is not None:
                        keep = stacked.sum(axis=0) >= thresh
                    elif how == "all":
                        keep = stacked.any(axis=0)
                    else:
                        keep = stacked.all(axis=0)
                    return valid & keep

                self._jit_cache[key] = jax.jit(compute)
            mask = self._jit_cache[key](
                dict(jdf.device_cols), dict(jdf.null_masks), jdf.device_valid_mask()
            )
            return JaxDataFrame(
                mesh=self._mesh,
                _internal=dict(
                    device_cols=dict(jdf.device_cols),
                    host_tbl=None,
                    row_count=-1,
                    valid_mask=mask,
                    nan_cols=jdf._nan_cols,
                    encodings=dict(jdf.encodings),
                    null_masks=dict(jdf.null_masks),
                    schema=jdf.schema,
                ),
            )
        with _host_op("dropna", "not_lowered") as sp:
            return self._back(
                self._host_engine.dropna(self._host(df, sp), how=how, thresh=thresh, subset=subset)
            )

    @traced_verb("engine.fillna")
    def fillna(self, df, value, subset=None) -> DataFrame:
        """All-device frames: fill NaN floats and null-masked numeric cells
        on device (filled masks clear). Fills targeting dictionary/datetime
        encoded columns go to the host engine."""
        jdf = self.to_df(df)
        if (
            isinstance(jdf, JaxDataFrame)
            and jdf.host_table is None
            and len(jdf.device_cols) == len(jdf.schema)
        ):
            import jax
            import jax.numpy as jnp

            # validate the value exactly like the host engine (no data moves)
            empty = ArrowDataFrame(None, jdf.schema)
            self._host_engine.fillna(empty, value, subset=subset)
            if isinstance(value, dict):
                fills = dict(value)
            else:
                fills = {c: value for c in (subset or jdf.schema.names)}
            if any(c in jdf.encodings for c in fills):
                with _host_op("fillna", "encoded_column") as sp:
                    return self._back(
                        self._host_engine.fillna(self._host(df, sp), value, subset=subset)
                    )
            masked_fills = frozenset(c for c in fills if c in jdf.null_masks)
            fill_sig = tuple(sorted((k, float(v)) for k, v in fills.items() if k in jdf.schema))
            key = ("fillna", fill_sig, tuple(jdf.schema.names), masked_fills)
            if key not in self._jit_cache:

                def compute(
                    dcols: Dict[str, Any], masks: Dict[str, Any]
                ) -> Dict[str, Any]:
                    out = dict(dcols)
                    for c, v in fills.items():
                        arr = dcols.get(c)
                        if arr is None:
                            continue
                        if c in masked_fills:
                            out[c] = jnp.where(
                                masks[c], jnp.asarray(v, arr.dtype), arr
                            )
                        elif jnp.issubdtype(arr.dtype, jnp.floating):
                            out[c] = jnp.where(jnp.isnan(arr), jnp.asarray(v, arr.dtype), arr)
                    return out

                self._jit_cache[key] = jax.jit(compute)
            new_cols = self._jit_cache[key](
                dict(jdf.device_cols), dict(jdf.null_masks)
            )
            new_masks = {
                c: m for c, m in jdf.null_masks.items() if c not in masked_fills
            }
            return JaxDataFrame(
                mesh=self._mesh,
                _internal=dict(
                    device_cols=new_cols,
                    host_tbl=None,
                    row_count=jdf._row_count,
                    valid_mask=jdf.valid_mask,
                    # filled columns become NaN-free — unless the fill value
                    # is itself NaN (a no-op fill must not fake the proof)
                    nan_cols=(
                        None
                        if jdf._nan_cols is None
                        else jdf._nan_cols
                        - {
                            c
                            for c, v in fills.items()
                            if not (isinstance(v, float) and v != v)
                        }
                    ),
                    encodings=dict(jdf.encodings),
                    null_masks=new_masks,
                    schema=jdf.schema,
                ),
            )
        with _host_op("fillna", "host_frame") as sp:
            return self._back(self._host_engine.fillna(self._host(df, sp), value, subset=subset))

    @traced_verb("engine.sample")
    def sample(self, df, n=None, frac=None, replace=False, seed=None) -> DataFrame:
        """frac-sampling on device: a Bernoulli mask ANDed into validity —
        zero data movement (n-sampling and replacement go host-side)."""
        jdf = self.to_df(df)
        if (
            frac is not None
            and n is None
            and not replace
            and isinstance(jdf, JaxDataFrame)
            and jdf.host_table is None
            and len(jdf.device_cols) > 0
        ):
            import jax
            import jax.numpy as jnp

            key = ("sample", jdf.mesh)
            if key not in self._jit_cache:

                def compute(valid: Any, rngkey: Any, p: Any) -> Any:
                    u = jax.random.uniform(rngkey, valid.shape)
                    return valid & (u < p)

                self._jit_cache[key] = jax.jit(compute)
            if seed is None:
                import numpy as np_

                seed = int(np_.random.default_rng().integers(0, 2**31 - 1))
            rngkey = jax.random.PRNGKey(seed)
            mask = self._jit_cache[key](
                jdf.device_valid_mask(), rngkey, float(frac)
            )
            return JaxDataFrame(
                mesh=self._mesh,
                _internal=dict(
                    device_cols=dict(jdf.device_cols),
                    host_tbl=None,
                    row_count=-1,
                    valid_mask=mask,
                    nan_cols=jdf._nan_cols,
                    encodings=dict(jdf.encodings),
                    null_masks=dict(jdf.null_masks),
                    schema=jdf.schema,
                ),
            )
        with _host_op("sample", "not_lowered") as sp:
            return self._back(
                self._host_engine.sample(self._host(df, sp), n=n, frac=frac, replace=replace, seed=seed)
            )

    @traced_verb("engine.take")
    def take(self, df, n, presort, na_position="last", partition_spec=None) -> DataFrame:
        """Global top-n by any number of device sort keys: per-shard
        lexicographic ``lax.sort`` takes each shard's first k rows, then an
        O(shards·n) host merge picks the global n.

        Exact in all cases the gate admits: each shard contributes its
        lexicographically-first min(k, valid) rows, so the merged pool is
        always a superset of the true global top-n — multi-key presorts,
        full-range int64 keys (no float64 scoring) and NaN tails included
        (XLA sorts NaN after all numbers, matching ``na_position="last"``;
        DESC negates floats / bit-inverts ints, both NaN/order preserving).
        """
        from ..collections.partition import parse_presort_exp
        from .streaming import is_stream_frame, streaming_take

        if is_stream_frame(df):
            # one-pass stream: running top-n buffers, O(n·keys + chunk)
            return streaming_take(
                self, df, n, presort, na_position, partition_spec
            )
        jdf = self.to_df(df)
        sorts = parse_presort_exp(presort) if presort else (
            partition_spec.presort if partition_spec is not None else {}
        )
        no_keys = partition_spec is None or len(partition_spec.partition_by) == 0

        def _sortable(c: str) -> bool:
            if c not in jdf.device_cols:
                return False
            enc = jdf.encodings.get(c)
            if enc is None:
                return True
            # sorted-dict codes and epoch ints order like their values
            return enc["kind"] == "datetime" or (
                enc["kind"] == "dict" and enc.get("sorted", False)
            )

        if (
            no_keys
            and len(sorts) > 0
            and na_position == "last"
            and isinstance(jdf, JaxDataFrame)
            and jdf.host_table is None
            and all(_sortable(c) for c in sorts)
            and n <= 4096
        ):
            import jax
            import jax.numpy as jnp
            import numpy as np_
            from jax.sharding import PartitionSpec as JP

            sort_items = list(sorts.items())
            dict_sort_cols = frozenset(
                c
                for c in sorts
                if jdf.encodings.get(c, {}).get("kind") == "dict"
            )
            masked = frozenset(jdf.null_masks)
            k = min(
                n,
                next(iter(jdf.device_cols.values())).shape[0]
                // num_row_shards(self._mesh),
            )
            if k > 0:
                mesh = jdf.mesh  # bind locally: the closure must not pin jdf
                mp = _safe_prefix("__mask__", jdf.schema.names)
                tvp = _safe_prefix("__take_valid__", jdf.schema.names)
                cache_key = (
                    "take",
                    tuple(sort_items),
                    k,
                    mesh,
                    tuple(jdf.schema.names),
                    dict_sort_cols,
                    masked,
                )
                if cache_key not in self._jit_cache:

                    def compute(
                        cols: Dict[str, Any], masks: Dict[str, Any], valid: Any
                    ):
                        def shard_fn(
                            c: Dict[str, Any], m: Dict[str, Any], v: Any
                        ):
                            # per-key (isnull, transformed-key) pairs: NULLs
                            # sort last within ties of earlier keys, exactly
                            # pandas na_position="last", for any asc/desc mix
                            ops: List[Any] = [jnp.logical_not(v)]  # valid first
                            for name, asc in sort_items:
                                key = c[name]
                                isnull = jnp.zeros(key.shape, dtype=bool)
                                if name in m:
                                    isnull = isnull | m[name]
                                if jnp.issubdtype(key.dtype, jnp.floating):
                                    isnull = isnull | jnp.isnan(key)
                                if name in dict_sort_cols:
                                    isnull = isnull | (key < 0)
                                if not asc:
                                    if jnp.issubdtype(key.dtype, jnp.floating):
                                        key = jnp.where(isnull, key, -key)
                                    elif key.dtype == jnp.bool_:
                                        key = jnp.logical_not(key)
                                    else:
                                        key = ~key  # monotone reversal
                                ops.extend([isnull, key])
                            iota = jax.lax.iota(jnp.int32, v.shape[0])
                            sorted_ops = jax.lax.sort(
                                tuple(ops) + (iota,), num_keys=len(ops)
                            )
                            perm = sorted_ops[-1][:k]
                            out = {name: arr[perm] for name, arr in c.items()}
                            for name, arr in m.items():
                                out[f"{mp}{name}"] = arr[perm]
                            out[tvp] = v[perm]
                            return out

                        return shard_map(
                            shard_fn,
                            mesh=mesh,
                            in_specs=(JP(ROW_AXIS), JP(ROW_AXIS), JP(ROW_AXIS)),
                            out_specs=JP(ROW_AXIS),
                        )(cols, masks, valid)

                    self._jit_cache[cache_key] = jax.jit(compute)
                outs = self._jit_cache[cache_key](
                    dict(jdf.device_cols),
                    dict(jdf.null_masks),
                    jdf.device_valid_mask(),
                )
                host = {
                    name: np_.asarray(jax.device_get(arr))
                    for name, arr in outs.items()
                }
                valid = host.pop(tvp)
                mask_cols = {
                    name[len(mp):]: host.pop(name)[valid]
                    for name in list(host)
                    if name.startswith(mp)
                }
                pdf = pd.DataFrame({k2: v2[valid] for k2, v2 in host.items()})
                for c, m in mask_cols.items():
                    pdf[c] = pdf[c].mask(m)
                # decode codes/epochs so host sorting and output use VALUES
                pdf = self._decode_partial_keys(jdf, pdf, {})
                pdf = pdf.sort_values(
                    [c for c, _ in sort_items],
                    ascending=[a for _, a in sort_items],
                    na_position="last",
                ).head(n)
                return self.to_df(
                    PandasDataFrame(
                        pdf[jdf.schema.names].reset_index(drop=True), jdf.schema
                    )
                )
        with _host_op("take", "not_lowered") as sp:
            return self._back(
                self._host_engine.take(
                    self._host(df, sp), n, presort, na_position=na_position, partition_spec=partition_spec
                )
            )

    def load_df(self, path, format_hint=None, columns=None, **kwargs) -> DataFrame:
        with get_tracer().span("io.read", annotate=True, format=format_hint) as sp:
            local = self._host_engine.load_df(path, format_hint=format_hint, columns=columns, **kwargs)
            if sp is not NULL_SPAN:
                sp.set(rows=local.count(), bytes=_host_bytes(local))
        return self.to_df(local)

    def save_df(
        self, df, path, format_hint=None, mode="overwrite",
        partition_spec=None, force_single=False, **kwargs,
    ) -> DataFrame:
        self._host_engine.save_df(
            self._host(df), path, format_hint=format_hint, mode=mode,
            partition_spec=partition_spec, force_single=force_single, **kwargs,
        )
        return df

    def convert_yield_dataframe(self, df: DataFrame, as_local: bool) -> DataFrame:
        return df.as_local() if as_local else df

    # ---- compiled derived ops ---------------------------------------------
    @traced_verb("engine.select")
    def select(
        self,
        df: DataFrame,
        cols: SelectColumns,
        where: Optional[ColumnExpr] = None,
        having: Optional[ColumnExpr] = None,
    ) -> DataFrame:
        from ..column.jax_eval import device_predicate_plan

        jdf = self.to_df(df)
        sc = cols.replace_wildcard(jdf.schema)
        # WHERE lowers to a device mask filter when possible
        if (
            where is not None
            and len(jdf.device_cols) > 0
            and jdf.host_table is None
        ):
            where_plan = device_predicate_plan(
                where, jdf.device_cols, jdf.encodings
            )
            if where_plan is not None:
                jdf = self.filter(jdf, where, _plan=where_plan)  # type: ignore
                where = None
        # grouped aggregation lowers to the device groupby
        agg_refused: Optional[str] = None
        if where is None and sc.has_agg and not sc.is_distinct:
            from ..collections.partition import PartitionSpec as _PSpec
            from ..column.functions import is_agg as _is_agg

            keys = [c for c in sc.all_cols if not _is_agg(c)]
            aggs = [c for c in sc.all_cols if _is_agg(c)]
            pre = _agg_pre_projection(jdf, keys, aggs)
            if isinstance(pre, str):
                agg_refused = pre
            else:
                work = jdf
                if len(pre["helpers"]) > 0:
                    # computed keys and expression arguments become helper
                    # columns of one device projection
                    with get_tracer().span(
                        "agg.project", annotate=True,
                        args=pre["made_args"], keys=pre["made_keys"],
                    ):
                        work = self._device_project(
                            jdf, SelectColumns(*pre["passthrough"], *pre["helpers"])
                        )
                agg_plan = _device_agg_plan(work, pre["by"], pre["aggs"])
                agg_refused = agg_plan if isinstance(agg_plan, str) else None
                if agg_refused is None:
                    res = self.aggregate(work, _PSpec(by=pre["by"]), pre["aggs"])
                    if len(pre["renames"]) > 0:
                        res = res.rename(pre["renames"])
                    if having is not None:
                        # the aggregate result is O(groups): host filter;
                        # aggregate subexpressions read their computed
                        # output columns (same contract as the oracle)
                        from ..column.eval import rewrite_having_aggs

                        with _host_op("filter", "having_after_agg") as sp:
                            res = self._back(
                                self._host_engine.filter(
                                    self._host(res, sp),
                                    rewrite_having_aggs(having, aggs),
                                )
                            )
                    # restore declared projection order
                    order = [c.output_name for c in sc.all_cols]
                    if res.schema.names != order:
                        res = res[order]
                    return res
        plain_cols = {
            k: v
            for k, v in jdf.device_cols.items()
            if k not in jdf.encodings and k not in jdf.null_masks
        }
        if (
            where is None
            and having is None
            and not sc.has_agg
            and not sc.is_distinct
            and len(jdf.device_cols) > 0
            and all(
                _is_passthrough(c, jdf.device_cols)
                or can_evaluate_on_device(c, plain_cols)
                for c in sc.all_cols
            )
        ):
            return self._device_project(jdf, sc)
        with _host_op("select", _select_refusal, jdf, sc, where, having, agg_refused) as sp:
            return self._back(
                self._host_engine.select(
                    self._host(jdf, sp), cols, where=where, having=having
                )
            )

    def _device_project(self, jdf: JaxDataFrame, sc: SelectColumns) -> DataFrame:
        import jax

        schema = sc.infer_schema(jdf.schema)
        exprs = sc.all_cols
        # pass-through named columns (any encoding) copy arrays + metadata;
        # only computed expressions go through the compiled projection
        passthrough = [c for c in exprs if _is_passthrough(c, jdf.device_cols)]
        computed = [c for c in exprs if not _is_passthrough(c, jdf.device_cols)]
        out_encodings: Dict[str, Any] = {}
        out_masks: Dict[str, Any] = {}
        out_cols: Dict[str, Any] = {}
        for c in passthrough:
            out_cols[c.output_name] = jdf.device_cols[c.name]
            if c.name in jdf.encodings:
                out_encodings[c.output_name] = jdf.encodings[c.name]
            if c.name in jdf.null_masks:
                out_masks[c.output_name] = jdf.null_masks[c.name]

        if len(computed) > 0:

            def project_columns(cols: Dict[str, Any]) -> Dict[str, Any]:
                import jax.numpy as jnp

                out = {}
                for c in computed:
                    v = evaluate_jnp(cols, c)
                    if not hasattr(v, "shape") or getattr(v, "ndim", 0) == 0:
                        n = next(iter(cols.values())).shape[0]
                        v = jnp.full((n,), v)
                    out[c.output_name] = v
                return out

            cache_key = ("project", tuple(c.__uuid__() for c in computed), jdf.mesh)
            if cache_key not in self._jit_cache:
                self._jit_cache[cache_key] = jax.jit(project_columns)
            out_cols.update(self._jit_cache[cache_key](dict(jdf.device_cols)))
        out_cols = {c.output_name: out_cols[c.output_name] for c in exprs}
        if schema is None:
            fields = []
            for c in exprs:
                t = c.infer_type(jdf.schema)
                fields.append(
                    pa.field(c.output_name, t if t is not None else pa.from_numpy_dtype(np.dtype(out_cols[c.output_name].dtype)))
                )
            schema = Schema(fields)
        # pass-through named columns keep their NaN-free proof; computed
        # expressions are conservatively maybe-NaN (left out of the set is
        # only safe when the set is known, so start from the source's)
        from ..column.expressions import _NamedColumnExpr

        nan_cols: Optional[set] = None
        if jdf._nan_cols is not None:
            nan_cols = set()
            for c in exprs:
                if isinstance(c, _NamedColumnExpr) and c.as_type is None:
                    if c.name in jdf._nan_cols:
                        nan_cols.add(c.output_name)
                else:
                    import numpy as _np

                    arr = out_cols[c.output_name]
                    if _np.issubdtype(_np.dtype(arr.dtype), _np.floating):
                        nan_cols.add(c.output_name)
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols=out_cols,
                host_tbl=None,
                row_count=jdf._row_count,
                valid_mask=jdf.valid_mask,
                nan_cols=nan_cols,
                encodings=out_encodings,
                null_masks=out_masks,
                schema=schema,
            ),
        )

    def _virtual_agg_array(self, jdf: JaxDataFrame, tag: str, src: str) -> Any:
        """Materialize a derived aggregation input for a null-masked 64-bit
        int column — exactness-preserving views the float64 NaN view can't
        give (SURVEY §7 hard parts; STATUS r2 known gap):

        - ``hi``/``lo``: NULL→0 value split into 32-bit halves, so
          SUM = Σhi·2³² + Σlo stays exact at any magnitude;
        - ``minfill``/``maxfill``: NULLs become the dtype extreme (the
          identity for min/max), nullability recovered from the mask count.
        """
        import jax
        import jax.numpy as jnp

        cache_key = ("vagg", tag, self._mesh)
        if tag == "ones":
            # COUNT(*)'s input: a ones column shaped like any device column
            # (validity masking happens inside the kernel)
            if cache_key not in self._jit_cache:
                self._jit_cache[cache_key] = jax.jit(
                    lambda a: jnp.ones(a.shape, jnp.int64)
                )
            probe = next(iter(jdf.device_cols.values()))
            return self._jit_cache[cache_key](probe)
        if cache_key not in self._jit_cache:

            def build(a: Any, m: Any, _tag: str = tag):
                if _tag == "notnull":
                    return jnp.logical_not(m).astype(jnp.int64)
                filled = jnp.where(m, jnp.zeros((), a.dtype), a)
                if _tag == "hi":
                    return filled >> 32
                if _tag == "lo":
                    return filled & jnp.asarray(0xFFFFFFFF, dtype=a.dtype)
                ii = jnp.iinfo(a.dtype)
                fill = ii.max if _tag == "minfill" else ii.min
                return jnp.where(m, jnp.asarray(fill, dtype=a.dtype), a)

            self._jit_cache[cache_key] = jax.jit(build)
        return self._jit_cache[cache_key](
            jdf.device_cols[src], jdf.null_masks[src]
        )

    def _try_dense_device_aggregate(
        self,
        jdf: JaxDataFrame,
        keys: List[str],
        plan: dict,
        agg_entries: List[Any],
        range_hint: Optional[Tuple[int, int]],
    ) -> Optional[DataFrame]:
        """Finish a dense-plan aggregate ON DEVICE — no host roundtrip.

        The dense kernel's outputs are already cross-shard merged, so for
        plain frames (single int key, no masks/dictionaries/virtual
        columns) the final table is computable in one more jitted step:
        ``key = kmin + arange``, ``valid = present > 0``, avg = sum/count,
        dtype casts to the declared schema. The result frame keeps its
        columns device-resident with an explicit valid mask and a LAZY row
        count — on a remote-chip link this removes the only per-call
        device→host transfer (the reference instead materializes backend
        results per op, e.g. pandas groupby output frames,
        /root/reference/fugue/execution/native_execution_engine.py:172).
        Returns None when ineligible (caller runs the fetch+host-merge
        plan)."""
        from ..ops.segment import _DENSE_MAX_RANGE, dense_buckets

        if range_hint is None:
            return None
        if plan["dict_srcs"] or plan["masked_srcs"]:
            return None
        if any(tag != "ones" for tag, _ in plan["virtual"].values()):
            # hi/lo/fill virtuals need the host-merge finish; the COUNT(*)
            # ones column is a plain int input the fused kernel handles
            return None
        if any(p.get("kind") not in ("pass", "avg") for p in plan["post"]):
            return None
        kmin, kmax = range_hint
        rng = kmax - kmin + 1
        if not (0 < rng <= _DENSE_MAX_RANGE):
            return None

        # predict kernel output dtypes; bail on any cast a NULL could break
        predicted: Dict[str, np.dtype] = {}
        for name, agg, arr, _ in agg_entries:
            predicted[name] = (
                np.dtype(np.int64)
                if agg == "count"
                else np.dtype(arr.dtype)
            )
        key_dt = _np_numeric_dtype(self._field_type(jdf.schema, keys[0]))
        if key_dt is None:
            return None
        spec_rows = _dense_finish_spec(plan, predicted)
        if spec_rows is None:
            return None
        buckets = dense_buckets(rng)
        outs = self._run_dense_fused(
            jdf, keys[0], agg_entries, kmin, buckets, tuple(spec_rows), key_dt.str
        )
        device_cols = {keys[0]: outs[0]}
        for (_, name, _, _), arr in zip(spec_rows, outs[2:]):
            device_cols[name] = arr
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols=device_cols,
                host_tbl=None,
                row_count=-1,
                valid_mask=outs[1],
                schema=plan["schema"],
            ),
        )

    @staticmethod
    def _field_type(schema: Schema, name: str) -> pa.DataType:
        return schema[name].type

    def _run_dense_fused(
        self,
        jdf: JaxDataFrame,
        key: str,
        agg_entries: List[Any],
        kmin: int,
        buckets: int,
        spec_rows: Tuple[Any, ...],
        key_dtype: str,
    ):
        """Dense kernel + finish traced into ONE program — one dispatch per
        aggregate call instead of three (mask/kernel/finish); per-program
        submission latency is the dominant cost on a remote-chip link."""
        import jax

        from ..ops.segment import dense_kernel_parts

        kernel, arrays, agg_sig = dense_kernel_parts(
            self._mesh, agg_entries, buckets
        )
        arr_names = tuple(s[0] for s in agg_sig)
        from ..ops.segment import _DENSE_SUM_BACKEND

        cache_key = (
            "dense_fused",
            self._mesh,
            buckets,
            agg_sig,
            spec_rows,
            key_dtype,
            _DENSE_SUM_BACKEND[0],
        )
        if cache_key not in self._jit_cache:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            fin = self._make_dense_finish(
                buckets, arr_names, spec_rows, key_dtype
            )

            def fused(karr: Any, kmin_s: Any, vm: Any, *arrs: Any):
                outs = kernel(karr, kmin_s, *arrs, vm)
                return fin(kmin_s, outs[0], *outs[1:])

            self._jit_cache[cache_key] = jax.jit(
                fused,
                out_shardings=NamedSharding(self._mesh, P(ROW_AXIS)),
            )
        return self._jit_cache[cache_key](
            jdf.device_cols[key],
            np.int64(kmin),
            jdf.device_valid_mask(),
            *arrays,
        )

    def _make_dense_finish(
        self,
        buckets: int,
        arr_names: Tuple[str, ...],
        spec_rows: Tuple[Tuple[str, str, Tuple[str, ...], str], ...],
        key_dtype: str,
    ):
        """(key, valid, *outs) builder over replicated dense-kernel outputs,
        padded to the row-shard multiple with padding marked invalid. A
        plain closure — it is traced inside the fused jit, whose
        out_shardings reshard the results onto the row axis."""
        import jax.numpy as jnp

        from ..parallel.mesh import num_row_shards, pad_rows

        shards = num_row_shards(self._mesh)
        padded = pad_rows(max(buckets, shards), shards)

        def fin(kmin: Any, present: Any, *aggs: Any):
            named = dict(zip(arr_names, aggs))
            key = (jnp.arange(buckets, dtype=jnp.int64) + kmin).astype(
                jnp.dtype(key_dtype)
            )
            valid = present > 0
            outs = []
            for kind, _, ins, tgt in spec_rows:
                if kind == "avg":
                    s = named[ins[0]].astype(jnp.float64)
                    c = named[ins[1]].astype(jnp.float64)
                    a = s / jnp.where(c == 0, jnp.nan, c)
                else:
                    a = named[ins[0]]
                outs.append(a.astype(jnp.dtype(tgt)))

            def _pad(a: Any) -> Any:
                return jnp.pad(a, (0, padded - buckets))

            return tuple(_pad(x) for x in (key, valid, *outs))

        return fin

    @traced_verb("engine.aggregate")
    def aggregate(
        self,
        df: DataFrame,
        partition_spec: Optional[PartitionSpec],
        agg_cols: List[ColumnExpr],
    ) -> DataFrame:
        """Two-phase device groupby when keys+values are device-resident."""
        from ..column.expressions import _FuncExpr, _NamedColumnExpr
        from ..ops.segment import device_groupby_partials, merge_partials
        from .streaming import is_stream_frame, streaming_dense_aggregate

        if is_stream_frame(df):
            # one-pass stream: chunked ingestion + device-resident
            # accumulators (out-of-core); ineligible plans fall through to
            # materialization below
            res = streaming_dense_aggregate(self, df, partition_spec, agg_cols)
            if res is not None:
                return res
            self.log.warning(
                "streaming aggregate ineligible for this plan; "
                "materializing the stream"
            )
        jdf = self.to_df(df)
        keys = list(partition_spec.partition_by) if partition_spec is not None else []
        plan = _device_agg_plan(jdf, keys, agg_cols)
        if isinstance(plan, str):
            with _host_op("aggregate", "agg_plan_refused", plan) as sp:
                return self._back(
                    self._host_engine.aggregate(self._host(df, sp), partition_spec, agg_cols)
                )
        # dict codes / epoch ints group by device identity; nullable keys add
        # their mask as an extra key so NULL is its own group
        key_cols, mask_names = self._group_key_cols(jdf, keys)
        value_arrs = {}
        for src in {s for _, _, s in plan["aggs"]}:
            if src in plan["virtual"]:
                value_arrs[src] = self._virtual_agg_array(
                    jdf, *plan["virtual"][src]
                )
                continue
            arr = jdf.device_cols[src]
            if src in plan["dict_srcs"]:
                # sorted-dict codes → NaN-null float view (−1 code = NULL)
                cache_key = ("codeview", jdf.mesh)
                if cache_key not in self._jit_cache:
                    import jax
                    import jax.numpy as jnp

                    self._jit_cache[cache_key] = jax.jit(
                        lambda a: jnp.where(
                            a < 0, jnp.nan, a.astype(jnp.float64)
                        )
                    )
                arr = self._jit_cache[cache_key](arr)
            elif src in plan["masked_srcs"]:
                # nullable int/bool value → float64 view with NaN as NULL
                # (exact: 64-bit ints with nulls were rejected in the plan)
                cache_key = ("nullview", jdf.mesh)
                if cache_key not in self._jit_cache:
                    import jax
                    import jax.numpy as jnp

                    self._jit_cache[cache_key] = jax.jit(
                        lambda a, m: jnp.where(
                            m, jnp.nan, a.astype(jnp.float64)
                        )
                    )
                arr = self._jit_cache[cache_key](arr, jdf.null_masks[src])
            value_arrs[src] = arr
        # keys of known range pack into one dense group index: dictionary
        # codes (-1 is NULL) and null masks
        key_ranges = {mn: (0, 1) for mn in mask_names.values()}
        for k in keys:
            enc = jdf.encodings.get(k)
            if enc is not None and enc["kind"] == "dict":
                key_ranges[k] = (-1, len(enc["dictionary"]) - 1)
        # single plain-int key: reuse the frame's cached range probe so
        # repeated aggregates don't re-pay the device→host roundtrip
        range_hint = None
        if (
            len(keys) == 1
            and len(mask_names) == 0
            and key_cols.get(keys[0]) is jdf.device_cols.get(keys[0])
            and np.issubdtype(
                np.dtype(jdf.device_cols[keys[0]].dtype), np.integer
            )
        ):
            range_hint = jdf.key_range(keys[0])
            key_ranges[keys[0]] = range_hint
        agg_entries = [
            (
                name,
                agg,
                value_arrs[src],
                (
                    # virtual arrays (hi/lo/notnull/min-max fills) are
                    # pre-filled plain ints — never NaN-aware
                    False
                    if src in plan["virtual"]
                    else (
                        jdf.maybe_nan(src)
                        or src in plan["masked_srcs"]
                        or src in plan["dict_srcs"]
                    )
                ),
            )
            for name, agg, src in plan["aggs"]
        ]
        res = self._try_dense_device_aggregate(
            jdf, keys, plan, agg_entries, range_hint
        )
        if res is not None:
            return res
        partials = device_groupby_partials(
            self._mesh,
            key_cols,
            agg_entries,
            jdf.device_valid_mask(),
            key_ranges=key_ranges,
        )
        merged = merge_partials(
            partials,
            keys + list(mask_names.values()),
            [(n, a) for n, a, _ in plan["aggs"]],
        )
        merged = self._decode_partial_keys(jdf, merged, mask_names)
        # finalize: avg = sum/count; restore declared output order and names
        out = pd.DataFrame()
        for k in keys:
            out[k] = merged[k]
        for spec in plan["post"]:
            out[spec["name"]] = spec["fn"](merged)
        out_schema = plan["schema"]
        return self.to_df(PandasDataFrame(out, out_schema))


def _sorted_union_dictionary(pieces: "List[pa.Array]") -> pa.Array:
    """Distinct sorted union of dictionary arrays — THE canonical way a
    union dictionary is built everywhere (device union, multi-host comap
    reassembly), so code order == lexicographic order stays true."""
    u = pa.concat_arrays(pieces).unique().drop_null()
    order = pa.compute.sort_indices(u)
    return u.take(order)


def _dict_mapping(local_dict: pa.Array, union_dict: pa.Array) -> np.ndarray:
    """Index table from local dictionary positions to union positions.

    Apply as ``code >= 0 ? table[code] : -1`` (−1 is the NULL code). An
    empty local dictionary yields a single ``-1`` placeholder so device
    gathers stay in-bounds."""
    mapped = np.asarray(
        pa.compute.index_in(local_dict, value_set=union_dict).to_numpy(
            zero_copy_only=False
        )
    )
    if mapped.size == 0:
        mapped = np.asarray([-1])
    return mapped.astype(np.int32)


def _allgather_dictionaries(
    dicts: "Dict[str, pa.Array]",
) -> "Dict[str, pa.Array]":
    """Union string dictionaries across every process of the multi-host
    runtime, deterministically, in ONE exchange for all columns.

    All local dictionaries pack into a single tagged arrow table,
    serialize to an IPC buffer, and allgather exactly twice (lengths, then
    buffers padded to the global max so shapes agree) regardless of how
    many string columns the frame has. Every process deserializes all
    buffers and computes the IDENTICAL sorted distinct union per column —
    which is what makes the union dictionaries safe to store in frame
    metadata (divergent metadata would desynchronize later jitted
    programs into collective deadlock).
    """
    from jax.experimental import multihost_utils

    names = sorted(dicts)
    tags = np.concatenate(
        [np.full(len(dicts[n]), i, dtype=np.int32) for i, n in enumerate(names)]
    ) if len(names) > 0 else np.zeros(0, dtype=np.int32)
    vals = (
        pa.concat_arrays([dicts[n].cast(pa.large_string()) for n in names])
        if len(names) > 0
        else pa.array([], type=pa.large_string())
    )
    t = pa.table({"tag": pa.array(tags, pa.int32()), "val": vals})
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, t.schema) as w:
        w.write_table(t)
    buf = np.frombuffer(sink.getvalue(), dtype=np.uint8)
    lens = np.asarray(
        multihost_utils.process_allgather(np.asarray([len(buf)]))
    ).reshape(-1)
    mx = int(lens.max())
    padded = np.zeros(mx, dtype=np.uint8)
    padded[: len(buf)] = buf
    gathered = np.asarray(
        multihost_utils.process_allgather(padded)
    ).reshape(len(lens), mx)
    tag_pieces: List[Any] = []
    val_pieces: List[pa.Array] = []
    for i in range(len(lens)):
        rd = pa.ipc.open_stream(
            pa.py_buffer(gathered[i, : int(lens[i])].tobytes())
        ).read_all()
        tag_pieces.append(
            np.asarray(rd["tag"].to_numpy(zero_copy_only=False))
        )
        val_pieces.append(rd["val"].combine_chunks())
    all_tags = np.concatenate(tag_pieces) if tag_pieces else np.zeros(0, np.int32)
    all_vals = (
        pa.concat_arrays([p.cast(pa.large_string()) for p in val_pieces])
        if val_pieces
        else pa.array([], type=pa.large_string())
    )
    out: Dict[str, pa.Array] = {}
    for i, n in enumerate(names):
        sel = all_vals.take(pa.array(np.nonzero(all_tags == i)[0]))
        out[n] = _sorted_union_dictionary([sel])
    return out


def _null_safe_key(kv: Any) -> tuple:
    """Group-key tuple with every null (None/NaN/NaT) normalized to None.

    NaN group keys break cross-frame alignment: since Python 3.10
    ``hash(nan)`` is identity-based, so each frame's own NaN object forms
    its OWN dict key and the two sides' NULL groups never pair up in
    comap (observed as a full_outer zip splitting the NULL group)."""
    kt = kv if isinstance(kv, tuple) else (kv,)
    out = []
    for v in kt:
        try:
            isna = pd.isna(v)
        except Exception:
            isna = False
        out.append(None if isna is True else v)
    return tuple(out)


def _np_numeric_dtype(tp: pa.DataType) -> Optional[np.dtype]:
    if pa.types.is_integer(tp) or pa.types.is_floating(tp):
        return np.dtype(tp.to_pandas_dtype())
    return None


def _dense_finish_spec(
    plan: dict, predicted: Dict[str, np.dtype]
) -> Optional[Tuple[Tuple[str, str, Tuple[str, ...], str], ...]]:
    """(kind, name, input table names, target dtype) rows driving the
    on-device dense finish, or None when any declared-schema cast could
    corrupt a NULL. ``predicted`` maps each kernel output name to its
    actual table dtype. Factored out of the device-resident aggregate so
    the lowered-segment program validates casts identically."""
    spec_rows: List[Tuple[str, str, Tuple[str, ...], str]] = []
    for p, field_name in zip(plan["post"], plan["schema"].names[1:]):
        tgt = _np_numeric_dtype(plan["schema"][field_name].type)
        if tgt is None:
            return None
        if p["kind"] == "avg":
            ins: Tuple[str, ...] = (f"{p['name']}__sum", f"{p['name']}__cnt")
            src_dt = np.dtype(np.float64)
        else:
            ins = (p["name"],)
            src_dt = predicted[p["name"]]
        if src_dt.kind == "f" and tgt.kind != "f":
            return None  # NaN (NULL) would not survive the cast
        if src_dt.kind not in ("i", "u", "f") or tgt.kind not in ("i", "u", "f"):
            return None
        spec_rows.append((p["kind"], p["name"], ins, tgt.str))
    return tuple(spec_rows)


def _is_passthrough(c: ColumnExpr, device_cols: Any) -> bool:
    """A bare (possibly renamed) named column over a device column — copies
    arrays and metadata without evaluation, so any encoding is fine."""
    from ..column.expressions import _NamedColumnExpr

    return (
        isinstance(c, _NamedColumnExpr)
        and not c.wildcard
        and c.as_type is None
        and c.name in device_cols
    )


def _host_op(op: str, reason: Any, *why: Any) -> Any:
    """The ``host.op`` span around one fallback of a verb into the host
    engine; ``JaxExecutionEngine._host`` adds the rows it fetches to
    ``rows_in``. ``reason`` names why the device path refused: a token,
    joined with ``why`` as ``token:why``, or a function of ``why`` that
    builds one. Nothing is built while tracing is off."""
    tr = get_tracer()
    if not tr.enabled:
        return NULL_SPAN
    if callable(reason):
        reason = reason(*why)
    elif why:
        reason = ":".join([reason, *map(str, why)])
    return tr.span("host.op", annotate=True, op=op, reason=reason, rows_in=0)


def _select_refusal(
    jdf: JaxDataFrame,
    sc: SelectColumns,
    where: Optional[ColumnExpr],
    having: Optional[ColumnExpr],
    agg_refused: Optional[str],
) -> str:
    """Why ``select`` runs on the host: the first device path that refused."""
    if len(jdf.device_cols) == 0:
        return "host_frame"
    if where is not None:
        return "where_not_lowered"
    if agg_refused is not None:
        return f"agg_plan_refused:{agg_refused}"
    if sc.is_distinct:
        return "distinct"
    if having is not None:
        return "having"
    return "projection_not_lowered"


def _host_bytes(df: DataFrame) -> int:
    """The in-memory size of a host frame, as its arrow or pandas form
    reports it (0 where neither is at hand)."""
    native = df.native
    if isinstance(native, pa.Table):
        return int(native.nbytes)
    if isinstance(native, pd.DataFrame):
        return int(native.memory_usage(index=False).sum())
    return 0


def _agg_pre_projection(
    jdf: JaxDataFrame, keys: List[ColumnExpr], aggs: List[ColumnExpr]
) -> Union[dict, str]:
    """A grouped select in the device aggregate's terms, where every group
    key and every aggregate argument is a column of one frame.

    A renamed key groups by its source column and takes its name back
    after the aggregate (``renames``). Any other computed key, and every
    argument that is not a bare column, becomes a helper column that one
    device projection computes beside the source columns it passes through;
    arguments of the same structure share one helper. An integer summed or
    averaged is widened to int64 first, as the host sums it. Returns the
    ``host.op`` reason instead when a key or an argument cannot be
    evaluated over the frame's plain device columns."""
    from ..column.expressions import (
        _FuncExpr,
        _LitColumnExpr,
        _NamedColumnExpr,
        col,
        structural_key,
    )

    if len(keys) == 0:
        return "no_keys"
    plain = {
        k: v
        for k, v in jdf.device_cols.items()
        if k not in jdf.encodings and k not in jdf.null_masks
    }
    agg_names = {c.output_name for c in aggs}
    prefix = _safe_prefix(
        "__agg_", jdf.schema.names, agg_names, [k.output_name for k in keys]
    )
    sources: Dict[str, None] = {}  # passed through, in first-use order
    helpers: Dict[str, ColumnExpr] = {}  # structural key -> aliased expr
    by: List[str] = []
    renames: Dict[str, str] = {}
    made_keys: List[str] = []
    made_args: List[str] = []
    for k in keys:
        name = k.output_name
        if isinstance(k, _NamedColumnExpr) and k.as_type is None and not k.wildcard:
            if k.name not in jdf.device_cols:
                return "key_not_on_device"
            if k.name not in by and k.name not in agg_names:
                by.append(k.name)
                sources[k.name] = None
                if name != k.name:
                    renames[k.name] = name
                continue
        elif (
            isinstance(k, _LitColumnExpr)
            or name == ""
            or not can_evaluate_on_device(k, plain)
        ):
            return "computed_keys"
        # a computed key, or a source already grouped by under another name
        h = f"{prefix}key{len(made_keys)}"
        helpers[h] = k.alias(h)
        made_keys.append(h)
        by.append(h)
        renames[h] = name
    dev_aggs: List[ColumnExpr] = []
    for c in aggs:
        arg = c.args[0] if isinstance(c, _FuncExpr) and len(c.args) == 1 else None
        if (
            arg is None
            or (isinstance(arg, _NamedColumnExpr) and arg.as_type is None)
            or (
                c.func.upper() == "COUNT"
                and isinstance(arg, _LitColumnExpr)
                and arg.value is not None
            )
        ):
            # bare columns, COUNT(*) and COUNT(1): the planner's own terms
            if isinstance(arg, _NamedColumnExpr) and not arg.wildcard:
                if arg.name not in jdf.device_cols:
                    return "value_not_on_device"
                sources[arg.name] = None
            dev_aggs.append(c)
            continue
        tp = arg.infer_type(jdf.schema)
        if (
            tp is None
            or not (pa.types.is_integer(tp) or pa.types.is_floating(tp))
            or not can_evaluate_on_device(arg, plain)
        ):
            return "agg_of_expression"
        if c.func.upper() in ("SUM", "AVG") and pa.types.is_integer(tp):
            arg = arg.cast(pa.int64())
        key = structural_key(arg)
        if key not in helpers:
            h = f"{prefix}arg{len(made_args)}"
            helpers[key] = arg.alias(h)
            made_args.append(h)
        dev = c._copy()
        dev._args = [col(helpers[key].output_name)]
        dev_aggs.append(dev)
    return {
        "passthrough": [col(s) for s in sources],
        "helpers": list(helpers.values()),
        "by": by,
        "renames": renames,
        "aggs": dev_aggs,
        "made_keys": made_keys,
        "made_args": made_args,
    }


def _device_agg_plan(
    jdf: JaxDataFrame, keys: List[str], agg_cols: List[ColumnExpr]
) -> Union[dict, str]:
    """The device-aggregation plan, or a short token naming why the device
    aggregate refuses (the ``reason`` of a ``host.op`` span)."""
    from ..column.expressions import _FuncExpr, _NamedColumnExpr
    from ..column import functions as ff

    if len(keys) == 0:
        return "no_keys"
    if not all(k in jdf.device_cols for k in keys):
        return "key_not_on_device"
    aggs: List[Any] = []
    post: List[dict] = []
    virtual: Dict[str, Any] = {}  # vname -> (tag, real src)
    masked_srcs: set = set()
    dict_srcs: set = set()
    fields: List[pa.Field] = [jdf.schema[k] for k in keys]
    from ..column.expressions import _LitColumnExpr

    for c in agg_cols:
        if not isinstance(c, _FuncExpr) or not c.is_agg or c.is_distinct:
            return "agg_not_plain"
        if len(c.args) != 1:
            return "agg_args"
        if c.func.upper() == "COUNT" and (
            (
                isinstance(c.args[0], _LitColumnExpr)
                and c.args[0].value is not None  # COUNT(NULL) is 0, not *
            )
            or (
                isinstance(c.args[0], _NamedColumnExpr)
                and c.args[0].name == "*"
            )
        ):
            # COUNT(*) / COUNT(1): every row in the group counts, NULLs
            # included — a ones column summed under the validity mask
            name = c.output_name
            if name == "":
                return "agg_unnamed"
            virtual["__ones__"] = ("ones", None)
            aggs.append((name, "sum", "__ones__"))
            post.append(
                {"name": name, "kind": "pass", "fn": (lambda m, _n=name: m[_n])}
            )
            tp = c.infer_type(jdf.schema)
            fields.append(pa.field(name, tp if tp is not None else pa.int64()))
            continue
        if not isinstance(c.args[0], _NamedColumnExpr):
            return "agg_of_expression"
        src = c.args[0].name
        func = c.func.upper()
        if src not in jdf.device_cols:
            return "value_not_on_device"
        enc = jdf.encodings.get(src)
        if enc is not None:
            # sorted-dictionary strings: code order == value order, so
            # MIN/MAX/COUNT reduce over codes (as NaN-null float views) and
            # the min/max code decodes back to its string
            if not (
                enc["kind"] == "dict"
                and enc.get("sorted")
                and func in ("MIN", "MAX", "COUNT")
            ):
                return "encoded_value"
            dict_srcs.add(src)
        big_int_masked = False
        if src in jdf.null_masks:
            import numpy as np_

            dt = np_.dtype(jdf.device_cols[src].dtype)
            if dt.kind == "u" and dt.itemsize >= 8:
                # uint64 > 2^63 has no faithful pandas/post-processing
                # representation here — host engine computes it exactly
                return "uint64_with_nulls"
            if dt.kind == "i" and dt.itemsize >= 8:
                # int64 with NULLs: the float64 NaN view loses exactness
                # past 2^53 — SUM/AVG split into hi/lo 32-bit halves
                # (exact), MIN/MAX fill NULLs with dtype extremes, counts
                # come from the null mask
                big_int_masked = True
            else:
                masked_srcs.add(src)
        name = c.output_name
        if name == "":
            return "agg_unnamed"
        tp = c.infer_type(jdf.schema)
        if big_int_masked:
            if func not in ("SUM", "AVG", "MIN", "MAX", "COUNT"):
                return "agg_func"
            nn = f"{name}__nn"
            if func in ("SUM", "AVG"):
                virtual[f"{src}__hi__"] = ("hi", src)
                virtual[f"{src}__lo__"] = ("lo", src)
                aggs.append((f"{name}__hi", "sum", f"{src}__hi__"))
                aggs.append((f"{name}__lo", "sum", f"{src}__lo__"))
                virtual[f"{src}__nn__"] = ("notnull", src)
                aggs.append((nn, "sum", f"{src}__nn__"))
                if func == "SUM":
                    post.append(
                        {
                            "name": name,
                            # exact int64 reassembly; SUM over an all-NULL
                            # group is NULL (SQL; the host's float64 NaN
                            # coerces to the same)
                            "fn": (
                                lambda m, _n=name: (
                                    m[f"{_n}__hi"].astype("int64") * (1 << 32)
                                    + m[f"{_n}__lo"].astype("int64")
                                )
                                .astype("Int64")
                                .where(m[f"{_n}__nn"] > 0)
                            ),
                        }
                    )
                else:  # AVG
                    post.append(
                        {
                            "name": name,
                            "fn": (
                                lambda m, _n=name: (
                                    m[f"{_n}__hi"].astype("float64") * (1 << 32)
                                    + m[f"{_n}__lo"].astype("float64")
                                )
                                / m[f"{_n}__nn"].where(m[f"{_n}__nn"] > 0)
                            ),
                        }
                    )
            elif func in ("MIN", "MAX"):
                tag = "minfill" if func == "MIN" else "maxfill"
                virtual[f"{src}__{tag}__"] = (tag, src)
                aggs.append((name, func.lower(), f"{src}__{tag}__"))
                virtual[f"{src}__nn__"] = ("notnull", src)
                aggs.append((nn, "sum", f"{src}__nn__"))
                post.append(
                    {
                        "name": name,
                        # Int64 extension keeps <NA> exact (a float NaN
                        # detour would corrupt values past 2^53)
                        "fn": (
                            lambda m, _n=name: m[_n]
                            .astype("Int64")
                            .where(m[f"{_n}__nn"] > 0)
                        ),
                    }
                )
            else:  # COUNT
                virtual[f"{src}__nn__"] = ("notnull", src)
                aggs.append((name, "sum", f"{src}__nn__"))
                post.append({"name": name, "fn": (lambda m, _n=name: m[_n])})
            fields.append(pa.field(name, tp if tp is not None else pa.float64()))
            continue
        if src in dict_srcs and func in ("MIN", "MAX"):
            dictionary = enc["dictionary"]

            def _decode(m: Any, _n: str = name, _d: Any = dictionary) -> Any:
                codes = m[_n]
                na = codes.isna()
                arr = pa.array(
                    codes.fillna(0).to_numpy().astype(np.int64),
                    mask=na.to_numpy() if na.any() else None,
                )
                return _d.take(arr).to_pandas()

            aggs.append((name, func.lower(), src))
            post.append({"name": name, "fn": _decode})
        elif func in ("SUM", "MIN", "MAX"):
            aggs.append((name, func.lower(), src))
            post.append(
                {"name": name, "kind": "pass", "fn": (lambda m, _n=name: m[_n])}
            )
        elif func == "COUNT":
            aggs.append((name, "count", src))
            post.append(
                {"name": name, "kind": "pass", "fn": (lambda m, _n=name: m[_n])}
            )
        elif func == "AVG":
            aggs.append((f"{name}__sum", "sum", src))
            aggs.append((f"{name}__cnt", "count", src))
            post.append(
                {
                    "name": name,
                    "kind": "avg",
                    "fn": (lambda m, _n=name: m[f"{_n}__sum"] / m[f"{_n}__cnt"]),
                }
            )
        else:
            return "agg_func"
        fields.append(pa.field(name, tp if tp is not None else pa.float64()))
    return {
        "aggs": aggs,
        "post": post,
        "schema": Schema(fields),
        "masked_srcs": masked_srcs,
        "dict_srcs": dict_srcs,
        "virtual": virtual,
    }


def _sniff_jax_func(map_func: Callable) -> Optional[Callable]:
    """Extract the raw jax function from a transformer runner, if the
    transformer is an interfaceless pure-jax function (input code "j")."""
    runner = getattr(map_func, "__self__", None)
    tf = getattr(runner, "transformer", None)
    wrapper = getattr(tf, "_wrapper", None)
    if wrapper is None or wrapper.input_code != "j" or wrapper.output_code != "j":
        return None
    if getattr(tf, "using_callback", False):
        return None
    return wrapper._func
