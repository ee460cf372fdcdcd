"""JaxDataFrame — rows sharded over a device mesh as columnar jax.Arrays.

The TPU-native distributed frame (SURVEY §7.1 "ShardedJaxDataFrame"):

- numeric/bool columns live on device, padded to a multiple of the mesh row
  axis and sharded ``NamedSharding(mesh, P("rows"))``; floats carry NULL as
  NaN;
- string columns are DICTIONARY-ENCODED: an int32 code array on device
  (−1 = NULL) plus the small host-side ``pa.Array`` dictionary — groupby /
  distinct / filter on strings run on device over codes, and string
  predicates evaluate host-side over the dictionary into a lookup table
  gathered by code (SURVEY §7 hard parts);
- nullable int/bool columns carry a per-column device null mask; timestamps
  and dates live as epoch int64/int32 with the original arrow type restored
  on conversion;
- anything else (binary, nested, decimal) stays host-resident as an arrow
  table aligned by row position;
- ``row_count`` tracks the unpadded logical length; padding is masked out in
  device ops and sliced off on conversion back to arrow.
"""

from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

from .._utils.assertion import assert_or_throw
from ..dataframe import ArrowDataFrame, DataFrame, LocalBoundedDataFrame
from ..dataframe.arrow_dataframe import build_arrow_table
from ..exceptions import FugueDataFrameInitError, FugueDataFrameOperationError
from ..parallel.mesh import ROW_AXIS, num_row_shards, pad_rows, row_sharding
from ..schema import Schema

_DEVICE_DTYPES = {
    "int8": np.int8,
    "int16": np.int16,
    "int32": np.int32,
    "int64": np.int64,
    "uint8": np.uint8,
    "uint16": np.uint16,
    "uint32": np.uint32,
    "uint64": np.uint64,
    "halffloat": np.float16,
    "float": np.float32,
    "double": np.float64,
    "bool": np.bool_,
}


# bulk-ingest tables below this size skip the background column pipeline:
# thread + queue setup (~1ms) would exceed the decode it hides
_MIN_PIPELINED_INGEST_BYTES = 8 << 20


def _is_device_type(f: pa.Field) -> bool:
    return str(f.type) in _DEVICE_DTYPES


def split_arrow_for_device(tbl: pa.Table) -> Any:
    """Back-compat split: (plain_device_cols, host_cols, nan_cols).

    Only null-free numeric/bool columns are treated as device candidates —
    the encoding-aware path is :func:`encode_arrow_for_device`.
    """
    device_cols, host_tbl, meta = encode_arrow_for_device(tbl, encode=False)
    return device_cols, host_tbl, meta["nan_cols"]


def _encode_column(col: Any, f: pa.Field, encode: bool) -> Any:
    """Encode ONE arrow column for the device: ``(arr, extra)``.

    ``arr`` is the device-bound numpy array, or None when the column stays
    host-resident. ``extra`` carries the per-column metadata: ``nan``
    (float column may hold NaN), ``encoding`` (dict/datetime internal
    representation) and ``null_mask`` (np bool array, True = NULL).
    The per-column unit of work for the pipelined ingest (`_from_arrow`) —
    the whole-table collector is :func:`encode_arrow_for_device`.
    """
    t = f.type
    if _is_device_type(f):
        if col.null_count == 0:
            arr = np.asarray(col.to_numpy(zero_copy_only=False))
            nan = np.issubdtype(arr.dtype, np.floating) and bool(
                np.isnan(arr).any()
            )
            return arr, ({"nan": True} if nan else {})
        if encode and pa.types.is_floating(t):
            # arrow float→numpy turns nulls into NaN — the device NULL
            arr = np.asarray(col.to_numpy(zero_copy_only=False))
            return arr, {"nan": True}
        if encode:  # nullable int/bool: value array + null mask
            mask = np.asarray(col.is_null().to_numpy(zero_copy_only=False))
            fill = False if pa.types.is_boolean(t) else 0
            vals = np.asarray(
                col.fill_null(fill).to_numpy(zero_copy_only=False)
            )
            return vals, {"null_mask": mask}
    if encode and (pa.types.is_string(t) or pa.types.is_large_string(t)):
        plain = (
            col.chunk(0)
            if isinstance(col, pa.ChunkedArray) and col.num_chunks == 1
            else (
                pa.array([], type=t)
                if isinstance(col, pa.ChunkedArray) and col.num_chunks == 0
                else col
            )
        )
        if isinstance(plain, pa.ChunkedArray):  # pragma: no cover
            plain = pa.concat_arrays(plain.chunks)
        d = plain.dictionary_encode()
        codes = np.asarray(
            d.indices.fill_null(-1).to_numpy(zero_copy_only=False)
        ).astype(np.int32)
        # SORT the dictionary so code order == lexicographic order:
        # MIN/MAX aggregates and presorts on the codes are then exact
        dictionary = d.dictionary.cast(t)
        if len(dictionary) > 1:
            order = np.asarray(
                pa.compute.sort_indices(dictionary).to_numpy(
                    zero_copy_only=False
                )
            )
            dictionary = dictionary.take(pa.array(order))
            inverse = np.empty(len(order), dtype=np.int32)
            inverse[order] = np.arange(len(order), dtype=np.int32)
            codes = np.where(codes >= 0, inverse[np.clip(codes, 0, None)], -1).astype(np.int32)
        return codes, {
            "encoding": {
                "kind": "dict",
                "dictionary": dictionary,
                "type": t,
                "sorted": True,
            }
        }
    if encode and (pa.types.is_timestamp(t) or pa.types.is_date(t)):
        storage = pa.int64() if not pa.types.is_date32(t) else pa.int32()
        ints = col.cast(storage)
        extra: Dict[str, Any] = {
            "encoding": {"kind": "datetime", "dictionary": None, "type": t}
        }
        if col.null_count > 0:
            extra["null_mask"] = np.asarray(
                col.is_null().to_numpy(zero_copy_only=False)
            )
            ints = ints.fill_null(0)
        return np.asarray(ints.to_numpy(zero_copy_only=False)), extra
    return None, None  # host-resident


def encode_arrow_for_device(tbl: pa.Table, encode: bool = True) -> Any:
    """Encode an arrow table for the device: (device_cols, host_tbl, meta).

    ``meta`` has:

    - ``nan_cols``: float columns that may contain NaN (device NULL);
    - ``encodings``: ``{name: {"kind": "dict"|"datetime", "dictionary":
      pa.Array|None, "type": pa.DataType}}`` — internal representations
      whose original arrow type is restored on conversion back;
    - ``null_masks``: ``{name: np bool array}`` — per-column null masks for
      nullable int/bool/datetime columns (True = NULL).
    """
    device_cols: Dict[str, np.ndarray] = {}
    host_names: List[str] = []
    meta: Dict[str, Any] = {"nan_cols": set(), "encodings": {}, "null_masks": {}}
    for i, f in enumerate(tbl.schema):
        arr, extra = _encode_column(tbl.column(i).combine_chunks(), f, encode)
        if arr is None:
            host_names.append(f.name)
            continue
        device_cols[f.name] = arr
        if extra.get("nan"):
            meta["nan_cols"].add(f.name)
        if "encoding" in extra:
            meta["encodings"][f.name] = extra["encoding"]
        if "null_mask" in extra:
            meta["null_masks"][f.name] = extra["null_mask"]
    host_tbl = tbl.select(host_names) if len(host_names) > 0 else None
    return device_cols, host_tbl, meta


def _nan_to_null(tbl: pa.Table) -> pa.Table:
    """Literal NaN → NULL in float columns (the device NULL convention),
    applied to host reads of never-ingested frames."""
    import pyarrow.compute as pc

    arrays: List[Any] = []
    changed = False
    for f in tbl.schema:
        col = tbl.column(f.name)
        if pa.types.is_floating(f.type):
            nan = pc.fill_null(pc.is_nan(col), False)
            if (pc.sum(nan).as_py() or 0) > 0:
                col = pc.if_else(nan, pa.scalar(None, f.type), col)
                changed = True
        arrays.append(col)
    if not changed:
        return tbl
    return pa.Table.from_arrays(arrays, schema=tbl.schema)


class JaxDataFrame(DataFrame):
    """Distributed frame over a jax device mesh."""

    def __init__(
        self,
        df: Any = None,
        schema: Any = None,
        mesh: Any = None,
        _internal: Optional[dict] = None,
        ingest_cache: Optional[bool] = None,
        ingest_prefetch_depth: Optional[int] = None,
        pipeline_stats: Any = None,
    ):
        if mesh is None:
            from ..parallel.mesh import build_mesh

            mesh = build_mesh()
        self._mesh = mesh
        # None → fall back to the global conf (engines pass their own conf)
        self._ingest_cache_opt = ingest_cache
        # pipelined ingest knobs (engines pass their conf's prefetch depth
        # and their PipelineStats sink; direct constructions use defaults)
        self._ingest_prefetch_depth = ingest_prefetch_depth
        self._pipeline_stats = pipeline_stats
        if _internal is not None:
            self._pending_tbl = None
            self._pending_src = None
            self._device_cols = _internal["device_cols"]
            self._host_tbl = _internal["host_tbl"]
            self._row_count = _internal["row_count"]
            self._valid_mask = _internal.get("valid_mask", None)
            # None = unknown → treat every float column as possibly-NaN
            self._nan_cols = _internal.get("nan_cols", None)
            self._encodings = _internal.get("encodings", None) or {}
            self._null_masks = _internal.get("null_masks", None) or {}
            super().__init__(_internal["schema"])
            return
        s = None if schema is None else (schema if isinstance(schema, Schema) else Schema(schema))
        if isinstance(df, JaxDataFrame):
            if s is not None and s != df.schema:
                # schema change requires real conversion, not a relabel
                self._set_pending(df.as_arrow().cast(s.pa_schema))
                super().__init__(s)
                return
            src_pending = getattr(df, "_pending_tbl", None)
            src_frame = getattr(df, "_pending_src", None)
            if src_pending is not None or src_frame is not None:
                self._set_pending(src_pending, src=src_frame)
                super().__init__(df.schema)
                return
            self._pending_tbl = None
            self._pending_src = None
            self._device_cols = dict(df._device_cols)
            self._host_tbl = df._host_tbl
            self._ingest_tbl = getattr(df, "_ingest_tbl", None)
            self._row_count = df._row_count
            self._valid_mask = df._valid_mask
            self._nan_cols = df._nan_cols
            self._encodings = dict(df._encodings)
            self._null_masks = dict(df._null_masks)
            super().__init__(df.schema)
            return
        if isinstance(df, DataFrame):
            if (s is None or s == df.schema) and df.is_local and df.is_bounded:
                # retain the SOURCE frame: host reads of a never-device-
                # touched frame return it as-is (zero conversions); arrow
                # conversion happens only if the device (or an arrow read)
                # actually needs it
                self._set_pending(None, src=df)  # type: ignore[arg-type]
                super().__init__(df.schema)
                return
            tbl = df.as_arrow()
            if s is not None and Schema(tbl.schema) != s:
                tbl = tbl.cast(s.pa_schema)
        else:
            tbl = build_arrow_table(df, s)
        self._set_pending(tbl)
        super().__init__(Schema(tbl.schema))

    def _set_pending(
        self, tbl: Optional[pa.Table], src: Optional[DataFrame] = None
    ) -> None:
        """LAZY ingestion: hold the arrow table (or the untouched source
        frame); device transfer happens on the FIRST device-facing access
        (`device_cols`/`null_masks`/…).

        Host reads (``as_arrow``/``as_pandas``/``count``) of a never-
        device-touched frame come straight from the pending table/source,
        so a host-map result that flows back to the host — the reference's
        default `transform()` shape, where the answer is fetched
        immediately — never pays a device round trip (or even an arrow
        conversion) at all."""
        import threading

        self._pending_tbl: Optional[pa.Table] = tbl
        self._pending_src: Optional[DataFrame] = src
        self._pending_lock = threading.Lock()
        self._device_cols = {}
        self._host_tbl = None
        self._ingest_tbl = None
        self._row_count = tbl.num_rows if tbl is not None else src.count()  # type: ignore[union-attr]
        self._valid_mask = None
        self._nan_cols = None
        self._encodings = {}
        self._null_masks = {}

    def _has_pending(self) -> bool:
        return (
            getattr(self, "_pending_tbl", None) is not None
            or getattr(self, "_pending_src", None) is not None
        )

    def _pending_table(self) -> pa.Table:
        """The pending arrow table, converting (and caching) from the
        retained source frame on first need. Callers must hold
        ``_pending_lock`` (or use ``_pending_snapshot``)."""
        if self._pending_tbl is None:
            self._pending_tbl = self._pending_src.as_arrow()  # type: ignore[union-attr]
        return self._pending_tbl

    def _pending_snapshot(self) -> Optional[pa.Table]:
        """Lock-guarded read of the pending table — safe against a
        concurrent ``_ensure_device`` nulling the pending fields."""
        if not self._has_pending():
            return None
        with self._pending_lock:
            if not self._has_pending():
                return None
            return self._pending_table()

    def _ensure_device(self) -> None:
        if not self._has_pending():
            return
        with self._pending_lock:
            if not self._has_pending():  # raced: another thread ingested
                return
            self._from_arrow(self._pending_table())
            self._pending_tbl = None
            self._pending_src = None

    def _from_arrow(self, tbl: pa.Table) -> None:
        import jax

        n = tbl.num_rows
        shards = num_row_shards(self._mesh)
        padded = pad_rows(max(n, shards), shards) if n > 0 else shards
        sharding = row_sharding(self._mesh)

        def _pad(arr: np.ndarray) -> np.ndarray:
            if len(arr) < padded:
                pad_val = np.zeros(padded - len(arr), dtype=arr.dtype)
                arr = np.concatenate([arr, pad_val])
            return arr

        # PIPELINED bulk ingest: a background producer decodes + pads the
        # NEXT column (arrow→numpy, dictionary encode, null masks) while
        # the consumer issues the H2D `device_put` of the CURRENT one —
        # the per-column analog of the chunk pipeline (docs/streaming.md).
        # Tiny tables skip the thread: its ~ms setup would dominate.
        from .pipeline import default_prefetch_depth, maybe_prefetch

        depth = (
            self._ingest_prefetch_depth
            if getattr(self, "_ingest_prefetch_depth", None) is not None
            else default_prefetch_depth()
        )
        if tbl.nbytes < _MIN_PIPELINED_INGEST_BYTES:
            depth = 0

        def produce() -> Any:
            for i, f in enumerate(tbl.schema):
                arr, extra = _encode_column(
                    tbl.column(i).combine_chunks(), f, True
                )
                if arr is None:
                    yield f.name, None, None, None
                    continue
                mask = extra.get("null_mask")
                yield f.name, _pad(arr), (
                    None if mask is None else _pad(mask)
                ), extra

        host_names: List[str] = []
        meta: Dict[str, Any] = {
            "nan_cols": set(),
            "encodings": {},
            "null_masks": {},
        }
        device_cols: Dict[str, Any] = {}
        device_masks: Dict[str, Any] = {}
        cols_it = maybe_prefetch(
            produce(),
            depth,
            stats=getattr(self, "_pipeline_stats", None),
            verb="ingest",
        )
        try:
            for name, arr, mask, extra in cols_it:
                if arr is None:
                    host_names.append(name)
                    continue
                device_cols[name] = jax.device_put(arr, sharding)
                if extra.get("nan"):
                    meta["nan_cols"].add(name)
                if "encoding" in extra:
                    meta["encodings"][name] = extra["encoding"]
                if mask is not None:
                    device_masks[name] = jax.device_put(mask, sharding)
        finally:
            cols_it.close()
        self._device_cols = device_cols
        host_tbl = tbl.select(host_names) if len(host_names) > 0 else None
        self._host_tbl = host_tbl
        # frames are immutable — the ingestion table stays valid for this
        # instance's lifetime, so host reads (as_arrow/as_pandas) skip the
        # device download entirely. EXCEPT when a float column holds literal
        # NaN values: the device treats NaN as NULL, so the decoded view
        # (NULL) and the raw ingest table (NaN) would diverge — no cache.
        # The cache pins the host copy for the frame's lifetime (~2x host
        # memory for ingest-heavy pipelines) — disable it globally with
        # fugue.tpu.ingest_cache=False when host RAM is the constraint.
        from ..constants import _FUGUE_GLOBAL_CONF, FUGUE_TPU_CONF_INGEST_CACHE

        opt = getattr(self, "_ingest_cache_opt", None)
        cacheable = (
            bool(opt)
            if opt is not None
            else bool(_FUGUE_GLOBAL_CONF.get(FUGUE_TPU_CONF_INGEST_CACHE, True))
        )
        if cacheable:
            for c in meta["nan_cols"]:
                col = tbl.column(c)
                literal_nans = pa.compute.sum(pa.compute.is_nan(col)).as_py()
                if literal_nans:
                    cacheable = False
                    break
        self._ingest_tbl = tbl if cacheable else None
        self._row_count = n
        # None = tail-padding semantics (rows [0, row_count) valid); a device
        # bool array = explicit per-row validity (result of device filters)
        self._valid_mask = None
        self._nan_cols = meta["nan_cols"]
        self._encodings = meta["encodings"]
        self._null_masks = device_masks

    # -- properties ---------------------------------------------------------
    @property
    def mesh(self) -> Any:
        return self._mesh

    @property
    def device_cols(self) -> Dict[str, Any]:
        self._ensure_device()
        return self._device_cols

    @property
    def host_table(self) -> Optional[pa.Table]:
        self._ensure_device()
        return self._host_tbl

    @property
    def valid_mask(self) -> Any:
        """Explicit device validity mask, or None for tail-padding."""
        return self._valid_mask

    def maybe_nan(self, name: str) -> bool:
        """Whether device float column ``name`` may contain NaN (i.e. NULL).

        False only when ingestion proved the column NaN-free; unknown
        provenance (e.g. transformer outputs) is conservatively True.
        """
        self._ensure_device()
        if self._nan_cols is None:
            return True
        return name in self._nan_cols

    @property
    def encodings(self) -> Dict[str, dict]:
        """Per-column internal device representations (dict/datetime)."""
        self._ensure_device()
        return self._encodings

    @property
    def null_masks(self) -> Dict[str, Any]:
        """Per-column device null masks (True = NULL) for nullable columns."""
        self._ensure_device()
        return self._null_masks

    @property
    def device_nbytes(self) -> int:
        """Resident byte footprint for cache/LRU accounting: device column
        buffers (plus masks) when materialized, else the pending host
        table's arrow bytes. Never forces ingestion."""
        if self._has_pending():
            with self._pending_lock:
                tbl = getattr(self, "_pending_tbl", None)
                if tbl is not None:
                    return int(tbl.nbytes)
                src = getattr(self, "_pending_src", None)
                if src is not None:
                    # estimate without forcing the arrow conversion
                    try:
                        return int(src.count()) * max(1, len(src.schema)) * 16
                    except Exception:
                        return 0
            return 0
        total = 0
        for arr in (getattr(self, "_device_cols", None) or {}).values():
            total += int(getattr(arr, "nbytes", 0) or 0)
        for arr in (getattr(self, "_null_masks", None) or {}).values():
            total += int(getattr(arr, "nbytes", 0) or 0)
        if getattr(self, "_valid_mask", None) is not None:
            total += int(getattr(self._valid_mask, "nbytes", 0) or 0)
        return total

    @property
    def has_encoded(self) -> bool:
        """True when any device column is not plainly-typed (encoded or
        masked) — device fast paths that assume plain semantics must gate
        on this."""
        self._ensure_device()
        return len(self._encodings) > 0 or len(self._null_masks) > 0

    def device_valid_mask(self) -> Any:
        """A device bool array marking valid rows (built from the row count
        when no explicit mask exists). Memoized — frames are immutable, so
        repeated ops over one frame do not re-run it."""
        self._ensure_device()
        if self._valid_mask is not None:
            return self._valid_mask
        cached = getattr(self, "_tail_mask_cache", None)
        if cached is not None:
            return cached
        import numpy as _np

        from ..ops.segment import _get_compiled_mask

        template = next(iter(self._device_cols.values()))
        mask = _get_compiled_mask(self._mesh)(template, _np.int64(self._row_count))
        self._tail_mask_cache = mask
        return mask

    def key_range(self, name: str) -> "Tuple[int, int]":
        """Cached ``(min, max)`` of integer device column ``name`` over
        valid rows — the probe behind dense-plan eligibility. Frames are
        immutable, so the probe runs at most once per (frame, column):
        repeated aggregates over a persisted frame pay its device→host
        fetch once. With no valid rows the kernel's fill
        values come back — ``(iinfo(dtype).max, iinfo(dtype).min)`` —
        so emptiness is detected as ``hi < lo``, never by sentinel."""
        cache = getattr(self, "_key_range_cache", None)
        if cache is None:
            cache = self._key_range_cache = {}
        if name not in cache:
            host_range = self._host_key_range(name)
            if host_range is not None:
                cache[name] = host_range
            else:
                import jax
                import numpy as _np

                from ..ops.segment import _get_compiled_minmax

                lo_a, hi_a = _get_compiled_minmax(self._mesh)(
                    self.device_cols[name], self.device_valid_mask()
                )
                # overlap the two fetches
                lo_a.copy_to_host_async()
                hi_a.copy_to_host_async()
                cache[name] = (
                    int(_np.asarray(jax.device_get(lo_a))[0]),
                    int(_np.asarray(jax.device_get(hi_a))[0]),
                )
        return cache[name]

    def _host_key_range(self, name: str) -> "Optional[Tuple[int, int]]":
        """Key range from the retained host/ingest arrow table when one
        exists — zero device traffic. Only valid for frames without an
        explicit device mask (all ingested rows valid)."""
        if self._valid_mask is not None:
            return None
        pend = self._pending_snapshot()
        if pend is not None:
            # never-ingested frame: probe the pending table, declining
            # exactly where ingestion would mask/encode (nulls present)
            if name not in pend.schema.names:
                return None
            if pend.column(name).null_count > 0:
                return None
            tbl = pend
        else:
            if name in self._null_masks or name in self._encodings:
                # the device column holds fill values / codes for these — a
                # host-side min/max (which skips NULLs) would disagree with
                # the device probe and produce wrong dense-plan bounds
                return None
            tbl = (
                self._ingest_tbl
                if getattr(self, "_ingest_tbl", None) is not None
                else self._host_tbl
            )
        if tbl is None or name not in tbl.schema.names:
            return None
        import pyarrow.compute as pc

        col = tbl.column(name)
        if not pa.types.is_integer(col.type):
            return None
        mm = pc.min_max(col)
        lo, hi = mm["min"].as_py(), mm["max"].as_py()
        if lo is None or hi is None:
            # empty / all-NULL: the device probe's fill-value convention
            # (hi < lo) signals emptiness to callers
            ii = np.iinfo(np.dtype(col.type.to_pandas_dtype()))
            return (ii.max, ii.min)
        return (int(lo), int(hi))

    @property
    def native(self) -> "JaxDataFrame":
        # the device frame IS the native object (like a Ray dataset); raw
        # buffers are available via .device_cols — returning those from
        # fa.* verbs would leak padding rows and drop the validity mask
        return self

    @property
    def is_local(self) -> bool:
        return False

    @property
    def is_bounded(self) -> bool:
        return True

    @property
    def num_partitions(self) -> int:
        return num_row_shards(self._mesh)

    @property
    def empty(self) -> bool:
        return self.count() == 0

    def count(self) -> int:
        if self._valid_mask is not None and self._row_count < 0:
            import jax as _jax

            self._row_count = int(_jax.device_get(self._valid_mask.sum()))
        return self._row_count

    # -- conversions --------------------------------------------------------
    def _decode_device_col(
        self, f: pa.Field, host: np.ndarray, nulls: Optional[np.ndarray]
    ) -> pa.Array:
        """Decode a (already row-filtered) host view of a device column back
        to its arrow form — NaN→NULL, dictionary codes→values, epochs→
        timestamps."""
        enc = self._encodings.get(f.name)
        if enc is None:
            # device convention: NaN float IS NULL — restore nulls on
            # the way out (skipped for columns proved NaN-free)
            if np.issubdtype(host.dtype, np.floating) and (
                self._nan_cols is None or f.name in self._nan_cols
            ):
                nn = np.isnan(host)
                nulls = nn if nulls is None else (nulls | nn)
            arr = pa.array(host, mask=nulls)
        elif enc["kind"] == "dict":
            # codes → dictionary values; −1 = NULL
            arr = enc["dictionary"].take(
                pa.array(host.astype(np.int64), mask=host < 0)
            )
        elif enc["kind"] == "datetime":
            arr = pa.array(host, mask=nulls).cast(enc["type"])
        else:  # pragma: no cover
            raise NotImplementedError(enc["kind"])
        return arr.cast(f.type, safe=False)

    def as_arrow(self, type_safe: bool = False) -> pa.Table:
        import jax

        pend = self._pending_snapshot()
        if pend is not None:
            # never ingested: the arrow table IS the data — but the device
            # convention (literal NaN == NULL) must hold for host reads too
            return _nan_to_null(pend)
        src = getattr(self, "_ingest_tbl", None)
        if src is not None:
            return src
        mask: Optional[np.ndarray] = None
        if self._valid_mask is not None:
            mask = np.asarray(jax.device_get(self._valid_mask))
        arrays: List[pa.Array] = []
        for f in self.schema.fields:
            if f.name in self._device_cols:
                host = np.asarray(jax.device_get(self._device_cols[f.name]))
                host = host[mask] if mask is not None else host[: self._row_count]
                nulls: Optional[np.ndarray] = None
                if f.name in self._null_masks:
                    nulls = np.asarray(jax.device_get(self._null_masks[f.name]))
                    nulls = (
                        nulls[mask] if mask is not None else nulls[: self._row_count]
                    )
                arrays.append(self._decode_device_col(f, host, nulls))
            else:
                assert self._host_tbl is not None
                col = self._host_tbl.column(f.name)
                if mask is not None:
                    col = col.filter(pa.array(mask[: len(col)]))
                else:
                    col = col.slice(0, self._row_count)
                arrays.append(col.combine_chunks())
        return pa.Table.from_arrays(arrays, schema=self.schema.pa_schema)

    @staticmethod
    def _local_np(arr: Any) -> np.ndarray:
        """This process's rows of a row-sharded device array, in global
        index order (multi-host safe: only addressable shards are read)."""
        shards = sorted(
            arr.addressable_shards,
            key=lambda s: (s.index[0].start or 0) if len(s.index) > 0 else 0,
        )
        return np.concatenate([np.asarray(s.data) for s in shards])

    def as_arrow_local(self) -> pa.Table:
        """THIS process's valid rows as an arrow table (per-host read for
        multi-host meshes; on one process it equals ``as_arrow``).

        Requires an all-device frame — host-resident columns are process-
        replicated and cannot be row-matched to local shards."""
        import jax

        assert_or_throw(
            self._host_tbl is None,
            FugueDataFrameOperationError(
                "as_arrow_local requires an all-device frame"
            ),
        )
        mask = self._local_np(self.device_valid_mask())
        arrays: List[pa.Array] = []
        for f in self.schema.fields:
            host = self._local_np(self._device_cols[f.name])[mask]
            nulls: Optional[np.ndarray] = None
            if f.name in self._null_masks:
                nulls = self._local_np(self._null_masks[f.name])[mask]
            arrays.append(self._decode_device_col(f, host, nulls))
        return pa.Table.from_arrays(arrays, schema=self.schema.pa_schema)

    def as_pandas_local(self) -> pd.DataFrame:
        from .._utils.arrow import pa_table_to_pandas

        return pa_table_to_pandas(self.as_arrow_local())

    def as_local_bounded(self) -> LocalBoundedDataFrame:
        src = getattr(self, "_pending_src", None)
        if src is not None and not self.has_metadata:
            # never device-touched: the retained source IS the data — a
            # host map over an ingested-then-fetched frame costs zero
            # conversions (pandas NaN and arrow NULL are the same thing on
            # the host side, so the NaN-to-NULL step isn't needed; shared
            # zero-copy, same contract as pandas_df_wrapper frames). With
            # metadata to attach, fall through: reset_metadata on the
            # shared source would mutate the caller's frame
            return src.as_local_bounded()
        res = ArrowDataFrame(self.as_arrow())
        if self.has_metadata:
            res.reset_metadata(self.metadata)
        return res

    def as_pandas(self) -> pd.DataFrame:
        src = getattr(self, "_pending_src", None)
        if src is not None:
            return src.as_pandas()
        from .._utils.arrow import pa_table_to_pandas

        return pa_table_to_pandas(self.as_arrow())

    def peek_array(self) -> List[Any]:
        self.assert_not_empty()
        return ArrowDataFrame(self.as_arrow().slice(0, 1)).peek_array()

    def as_array(
        self, columns: Optional[List[str]] = None, type_safe: bool = False
    ) -> List[List[Any]]:
        return ArrowDataFrame(self.as_arrow()).as_array(columns)

    def as_array_iterable(
        self, columns: Optional[List[str]] = None, type_safe: bool = False
    ) -> Iterable[List[Any]]:
        yield from ArrowDataFrame(self.as_arrow()).as_array_iterable(columns)

    # -- ops ----------------------------------------------------------------
    def _with(self, schema: Schema, device_cols: Dict[str, Any], host_tbl: Optional[pa.Table]) -> "JaxDataFrame":
        return JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols=device_cols,
                host_tbl=host_tbl,
                row_count=self._row_count,
                valid_mask=self._valid_mask,
                nan_cols=self._nan_cols,
                encodings={
                    k: v for k, v in self._encodings.items() if k in device_cols
                },
                null_masks={
                    k: v for k, v in self._null_masks.items() if k in device_cols
                },
                schema=schema,
            ),
        )

    def _lazy_project(self, schema: Schema) -> Optional["JaxDataFrame"]:
        """Column selection on a NOT-YET-INGESTED frame: select on the
        pending source/arrow table (zero-copy) and stay lazy, so dropped
        columns are never decoded or device_put — the contract the plan
        optimizer's column pruning relies on (docs/plan.md)."""
        if not self._has_pending():
            return None
        with self._pending_lock:
            if not self._has_pending():
                return None
            if self._pending_src is not None and self._pending_tbl is None:
                inner: DataFrame = self._pending_src[schema.names]
            else:
                inner = ArrowDataFrame(self._pending_table().select(schema.names))
        return JaxDataFrame(
            inner,
            mesh=self._mesh,
            ingest_cache=getattr(self, "_ingest_cache_opt", None),
            ingest_prefetch_depth=getattr(self, "_ingest_prefetch_depth", None),
            pipeline_stats=getattr(self, "_pipeline_stats", None),
        )

    def _drop_cols(self, cols: List[str]) -> DataFrame:
        schema = self.schema - cols
        lazy = self._lazy_project(schema)
        if lazy is not None:
            return lazy
        self._ensure_device()
        dc = {k: v for k, v in self._device_cols.items() if k in schema}
        keep_host = [n for n in schema.names if n not in dc]
        ht = self._host_tbl.select(keep_host) if len(keep_host) > 0 else None
        return self._with(schema, dc, ht)

    def _select_cols(self, cols: List[str]) -> DataFrame:
        schema = self.schema.extract(cols)
        lazy = self._lazy_project(schema)
        if lazy is not None:
            return lazy
        self._ensure_device()
        dc = {k: v for k, v in self._device_cols.items() if k in schema}
        keep_host = [n for n in schema.names if n not in dc]
        ht = self._host_tbl.select(keep_host) if len(keep_host) > 0 else None
        return self._with(schema, dc, ht)

    def rename(self, columns: Dict[str, str]) -> DataFrame:
        self._ensure_device()
        schema = self.schema.rename(columns)  # validates
        dc = {columns.get(k, k): v for k, v in self._device_cols.items()}
        ht = (
            self._host_tbl.rename_columns(
                [columns.get(n, n) for n in self._host_tbl.column_names]
            )
            if self._host_tbl is not None
            else None
        )
        res = JaxDataFrame(
            mesh=self._mesh,
            _internal=dict(
                device_cols=dc,
                host_tbl=ht,
                row_count=self._row_count,
                valid_mask=self._valid_mask,
                nan_cols=(
                    None
                    if self._nan_cols is None
                    else {columns.get(n, n) for n in self._nan_cols}
                ),
                encodings={
                    columns.get(k, k): v for k, v in self._encodings.items()
                },
                null_masks={
                    columns.get(k, k): v for k, v in self._null_masks.items()
                },
                schema=schema,
            ),
        )
        return res

    def alter_columns(self, columns: Any) -> DataFrame:
        new_schema = self.schema.alter(columns)
        if new_schema == self.schema:
            return self
        # simplest correct path: round trip through arrow
        return JaxDataFrame(
            ArrowDataFrame(self.as_arrow()).alter_columns(columns),
            mesh=self._mesh,
        )

    def head(self, n: int, columns: Optional[List[str]] = None) -> LocalBoundedDataFrame:
        tbl = self.as_arrow()
        if columns is not None:
            tbl = tbl.select(columns)
        return ArrowDataFrame(tbl.slice(0, n))
