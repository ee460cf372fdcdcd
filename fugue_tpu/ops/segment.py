"""Device-side segment (groupby) aggregation kernels.

The TPU-native replacement for the reference's backend-SQL groupby
(SURVEY §7.8): a two-phase aggregate —

1. **Device phase (the O(rows) work)**: inside ``shard_map`` each shard
   lexicographically sorts its rows by the key columns (``lax.sort`` with
   ``num_keys``), derives segment ids, reduces values with
   ``jax.ops.segment_*`` and packs group representatives to the front.
   Everything is static-shape; the data-dependent group count is carried as
   a per-shard scalar (SURVEY §7 hard parts: "mask, don't branch").
2. **Host phase (the O(groups) work)**: only the first ``max_groups`` rows
   per shard cross the wire (bounded transfer); partials merge by
   re-aggregation on host.

Compiled executables are cached per (mesh, key-count, agg signature) — jit
re-tracing happens only on dtype/shape changes.

Supported aggregations: sum, count, min, max (avg = sum+count at merge).
"""

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import collectives

_COMPILE_CACHE: Dict[Any, Any] = {}


def _norm_specs(
    agg_specs: Sequence[Tuple[Any, ...]]
) -> Tuple[Tuple[Tuple[str, str, int, bool], ...], int]:
    """Normalize agg specs to (name, agg, value_idx, nullable).

    Short forms: ``(name, agg)`` → one distinct value column per spec,
    nullable; ``(name, agg, vidx)`` → nullable. ``nullable`` means the
    (float) column may contain NaN — NaN-as-NULL handling is skipped for
    columns the caller proved null-free (the common pandas-ingestion case).
    Returns (normalized_specs, num_value_columns).
    """
    norm: List[Tuple[str, str, int, bool]] = []
    for i, spec in enumerate(agg_specs):
        if len(spec) == 2:
            norm.append((spec[0], spec[1], i, True))
        elif len(spec) == 3:
            norm.append((spec[0], spec[1], spec[2], True))
        else:
            norm.append(tuple(spec))  # type: ignore[arg-type]
    num_vals = max(s[2] for s in norm) + 1 if len(norm) > 0 else 0
    return tuple(norm), num_vals


def _agg_outputs(
    jnp: Any,
    specs: Sequence[Tuple[str, str, int, bool]],
    values: Sequence[Any],
    valid: Any,
    sum_of: Any,
    min_of: Any,
    max_of: Any,
    count_all: Any = None,
    merge_ops: Optional[Dict[str, Any]] = None,
) -> List[Any]:
    """Per-group aggregate arrays with NaN-as-NULL semantics — the single
    implementation shared by the sort+segment and dense-bucket kernels.

    ``sum_of``/``min_of``/``max_of`` inject the reduction primitive: they map
    a masked full-length row array to a per-group array. ``count_all`` is an
    optional precomputed per-group count of valid rows (the dense path's
    presence table), reused for NaN-free columns — when ``merge_ops`` is
    given it must already be cross-shard merged.

    ``merge_ops`` (optional ``{"sum", "min", "max"}`` → collective) merges
    the per-shard tables across shards ON DEVICE (psum/pmin/pmax) before
    the NULL-ify step, so the host receives one table instead of
    shards × buckets — the order matters: NULL-ify must see the GLOBAL
    non-null count, not the per-shard one.

    NaN in a nullable float column IS null: excluded from every aggregate
    (matching the oracle's dropna-first semantics) so results don't depend
    on shard layout; all-null groups come out NaN (NULL). ev/nn/agg results
    are memoized per value column — avg decomposes to sum+count of one
    column, and XLA does not reliably CSE scatter/segment reductions.
    """

    def _null_of(vidx: int) -> bool:
        nullable = any(s[2] == vidx and s[3] for s in specs)
        return nullable and jnp.issubdtype(values[vidx].dtype, jnp.floating)

    ev_cache: Dict[int, Any] = {}
    nn_cache: Dict[int, Any] = {}
    agg_cache: Dict[Tuple[str, int], Any] = {}

    def _merge(kind: str, table: Any) -> Any:
        return merge_ops[kind](table) if merge_ops is not None else table

    def _ev(vidx: int) -> Any:
        if vidx not in ev_cache:
            v = values[vidx]
            ev_cache[vidx] = (valid & ~jnp.isnan(v)) if _null_of(vidx) else valid
        return ev_cache[vidx]

    def _nn(vidx: int) -> Any:
        key = vidx if _null_of(vidx) else -1  # NaN-free columns share one count
        if key not in nn_cache:
            if key == -1 and count_all is not None:
                nn_cache[key] = count_all  # pre-merged by the caller
            else:
                nn_cache[key] = _merge(
                    "sum", sum_of(_ev(vidx).astype(jnp.int64))
                )
        return nn_cache[key]

    def _one(agg: str, vidx: int) -> Any:
        ckey = (agg, vidx)
        if ckey in agg_cache:
            return agg_cache[ckey]
        v = values[vidx]
        ev = _ev(vidx)
        may_null = _null_of(vidx)
        if agg == "sum":
            part = _merge("sum", sum_of(jnp.where(ev, v, jnp.zeros_like(v))))
            if may_null:
                part = jnp.where(_nn(vidx) > 0, part, jnp.nan)  # all-null → NULL
        elif agg == "count":
            part = _nn(vidx)
        elif agg == "min":
            part = _merge(
                "min",
                min_of(jnp.where(ev, v, jnp.full_like(v, _max_of(jnp, v.dtype)))),
            )
            if may_null:
                part = jnp.where(_nn(vidx) > 0, part, jnp.nan)
        elif agg == "max":
            part = _merge(
                "max",
                max_of(jnp.where(ev, v, jnp.full_like(v, _min_of(jnp, v.dtype)))),
            )
            if may_null:
                part = jnp.where(_nn(vidx) > 0, part, jnp.nan)
        else:  # pragma: no cover
            raise NotImplementedError(agg)
        agg_cache[ckey] = part
        return part

    return [_one(agg, vidx) for _, agg, vidx, _ in specs]


def _shard_kernel(num_keys: int, agg_specs: Sequence[Tuple[Any, ...]]):
    """Per-shard kernel: (keys..., values[num_vals], valid) →
    (nseg(1,), packed_keys...(n,), aggs...(n,)).

    ``aggs[i][j]`` is the reduction of segment j; ``packed_keys[i][j]`` its
    key — both valid for j < nseg. Value columns are deduplicated by index
    (see ``_norm_specs``) so identical reductions are computed once — XLA
    does not CSE scatter/segment ops reliably.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    specs, num_vals = _norm_specs(agg_specs)

    def kernel(*args: Any):
        keys = args[:num_keys]
        values = args[num_keys : num_keys + num_vals]
        valid = args[num_keys + num_vals]
        n = keys[0].shape[0]
        # sort invalid (padding) rows to the end, then lexicographic by keys;
        # sort a row-index payload instead of f64 values (narrow comparator)
        iota = lax.iota(jnp.int32, n)
        sorted_ops = lax.sort(
            (jnp.logical_not(valid),) + tuple(keys) + (iota,),
            num_keys=1 + num_keys,
        )
        s_keys = sorted_ops[1 : 1 + num_keys]
        perm = sorted_ops[-1]
        s_valid = valid[perm]
        s_values = [v[perm] for v in values]
        change = jnp.zeros(n, dtype=bool).at[0].set(True)
        for k in s_keys:
            change = change | jnp.concatenate(
                [jnp.ones(1, dtype=bool), k[1:] != k[:-1]]
            )
        change = change & s_valid
        nseg = change.sum(dtype=jnp.int32)
        seg_id = jnp.cumsum(change.astype(jnp.int32)) - 1
        seg_id = jnp.where(s_valid, seg_id, n - 1)
        outs = _agg_outputs(
            jnp,
            specs,
            s_values,
            s_valid,
            sum_of=lambda a: jax.ops.segment_sum(a, seg_id, num_segments=n),
            min_of=lambda a: jax.ops.segment_min(a, seg_id, num_segments=n),
            max_of=lambda a: jax.ops.segment_max(a, seg_id, num_segments=n),
        )
        # pack each segment's representative key to the front: stable argsort
        # on ~change puts segment-start rows first, in order
        starts = jnp.argsort(jnp.logical_not(change), stable=True)
        packed_keys = tuple(k[starts] for k in s_keys)
        return (nseg[None],) + packed_keys + tuple(outs)

    return kernel


def _max_of(jnp: Any, dt: Any) -> Any:
    return jnp.inf if jnp.issubdtype(dt, jnp.floating) else jnp.iinfo(dt).max


def _min_of(jnp: Any, dt: Any) -> Any:
    return -jnp.inf if jnp.issubdtype(dt, jnp.floating) else jnp.iinfo(dt).min


def _get_compiled_kernel(mesh: Any, num_keys: int, agg_sig: Tuple[Tuple[Any, ...], ...]):
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import ROW_AXIS

    agg_sig, num_vals = _norm_specs(agg_sig)
    cache_key = ("kernel", mesh, num_keys, agg_sig)
    if cache_key not in _COMPILE_CACHE:
        kernel = _shard_kernel(num_keys, agg_sig)
        n_in = num_keys + num_vals + 1
        n_out = 1 + num_keys + len(agg_sig)
        spec = P(ROW_AXIS)
        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=tuple(spec for _ in range(n_in)),
                out_specs=tuple(spec for _ in range(n_out)),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_slicer(mesh: Any, n_arrays: int, k: int):
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import ROW_AXIS

    cache_key = ("slice", mesh, n_arrays, k)
    if cache_key not in _COMPILE_CACHE:
        spec = P(ROW_AXIS)

        def take_k(*arrs: Any):
            return tuple(a[:k] for a in arrs)

        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                take_k,
                mesh=mesh,
                in_specs=tuple(spec for _ in range(n_arrays)),
                out_specs=tuple(spec for _ in range(n_arrays)),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_mask(mesh: Any):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import ROW_AXIS

    cache_key = ("mask", mesh)
    if cache_key not in _COMPILE_CACHE:

        def mask(template: Any, row_count: Any):
            def shard_fn(t: Any, rc: Any):
                n_local = t.shape[0]
                base = jax.lax.axis_index(ROW_AXIS).astype(jnp.int64) * n_local
                return base + jax.lax.iota(jnp.int64, n_local) < rc

            return shard_map(
                shard_fn,
                mesh=mesh,
                in_specs=(P(ROW_AXIS), P()),
                out_specs=P(ROW_AXIS),
            )(template, row_count)

        _COMPILE_CACHE[cache_key] = jax.jit(mask)
    return _COMPILE_CACHE[cache_key]


# max bucket table size for the dense (sort-free) groupby path
_DENSE_MAX_RANGE = 1 << 18

# float32 SUM engine inside the dense kernel: "scatter" (XLA scatter-add),
# "onehot" (chunked one-hot MXU matmul, jnp), or "pallas" (the Pallas TPU
# kernel in ops/pallas_groupby.py, at most its MAX_BUCKETS buckets; it
# raises above). Resolution order: env FUGUE_TPU_DENSE_SUM → the entry for
# jax.default_backend() under "dense_sum" in ``_tuned.json`` next to this
# file → "scatter". That entry is a record of the removed bench A/B: it
# holds only "cpu": "onehot", and nothing rewrites it (ROADMAP A4).
import json as _json
import os as _os
from jax import shard_map

_DENSE_SUM_BACKENDS = ("scatter", "onehot", "pallas")
_TUNED_PATH = _os.path.join(_os.path.dirname(__file__), "_tuned.json")


def _read_backend_env() -> str:
    raw = _os.environ.get("FUGUE_TPU_DENSE_SUM", "").strip().lower()
    if not raw:
        return ""
    if raw not in _DENSE_SUM_BACKENDS:
        raise ValueError(
            f"FUGUE_TPU_DENSE_SUM={raw!r} is not one of {_DENSE_SUM_BACKENDS}"
        )
    return raw


def _read_tuned_default() -> str:
    """Per-platform default recorded in ``_tuned.json``. Falls back to
    scatter — the safe choice on platforms never benchmarked."""
    try:
        with open(_TUNED_PATH) as f:
            tuned = _json.load(f).get("dense_sum", {})
    except Exception:
        return "scatter"
    import jax

    name = tuned.get(jax.default_backend(), "scatter")
    return name if name in _DENSE_SUM_BACKENDS else "scatter"


class _BackendBox:
    """Lazy one-slot holder: index 0 resolves env → tuned file → scatter on
    first read (after jax backend selection settles), then sticks."""

    def __init__(self) -> None:
        self._name: str = _read_backend_env()

    def __getitem__(self, i: int) -> str:
        if not self._name:
            self._name = _read_tuned_default()
        return self._name

    def __setitem__(self, i: int, name: str) -> None:
        self._name = name


_DENSE_SUM_BACKEND = _BackendBox()


def set_dense_sum_backend(name: str) -> None:
    if name not in _DENSE_SUM_BACKENDS:
        raise ValueError(f"unknown dense sum backend {name!r}")
    _DENSE_SUM_BACKEND[0] = name
    _COMPILE_CACHE.clear()  # compiled programs bake the backend in


def _get_compiled_minmax(mesh: Any):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import ROW_AXIS

    cache_key = ("minmax", mesh)
    if cache_key not in _COMPILE_CACHE:

        def mm(k: Any, valid: Any):
            def shard_fn(k_: Any, v_: Any):
                big = jnp.where(v_, k_, jnp.iinfo(k_.dtype).max)
                small = jnp.where(v_, k_, jnp.iinfo(k_.dtype).min)
                return (
                    collectives.pmin(big.min(), ROW_AXIS)[None],
                    collectives.pmax(small.max(), ROW_AXIS)[None],
                )

            return shard_map(
                shard_fn,
                mesh=mesh,
                in_specs=(P(ROW_AXIS), P(ROW_AXIS)),
                out_specs=(P(), P()),
            )(k, valid)

        _COMPILE_CACHE[cache_key] = jax.jit(mm)
    return _COMPILE_CACHE[cache_key]


def _get_compiled_dense(mesh: Any, buckets: int, agg_sig: Tuple[Tuple[str, str], ...]):
    """Sort-free per-shard groupby: scatter-add into a dense bucket table,
    merged ACROSS shards on device (psum/pmin/pmax over the rows axis).

    Applies when the key range fits ``buckets`` — the common case — and
    avoids ``lax.sort`` entirely (sorts are the slow path on TPU; scatter
    reductions vectorize on the VPU). The cross-shard merge rides ICI and
    leaves ONE replicated table, so the host transfer is O(buckets), not
    O(shards × buckets).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import ROW_AXIS

    agg_sig, num_vals = _norm_specs(agg_sig)
    cache_key = ("dense", mesh, buckets, agg_sig, _DENSE_SUM_BACKEND[0])
    if cache_key not in _COMPILE_CACHE:

        def kernel(k: Any, kmin: Any, *rest: Any):
            values = rest[:num_vals]
            valid = rest[num_vals]
            idx = jnp.where(valid, (k - kmin).astype(jnp.int32), buckets - 1)
            present = collectives.psum(
                jnp.zeros(buckets, dtype=jnp.int64).at[idx].add(
                    valid.astype(jnp.int64)
                ),
                ROW_AXIS,
            )
            def sum_of(a: Any) -> Any:
                if (
                    _DENSE_SUM_BACKEND[0] != "scatter"
                    and a.dtype == jnp.float32
                ):
                    # one-hot MXU matmul path (ops/pallas_groupby.py):
                    # scatter on TPU serializes; histograms ride the MXU.
                    # float32 only — the MXU has no 64-bit path, so f64
                    # exactness keeps the scatter/XLA-emulation route
                    from .pallas_groupby import bin_sum_idx

                    return bin_sum_idx(idx, a, buckets, _DENSE_SUM_BACKEND[0])
                return jnp.zeros(buckets, dtype=a.dtype).at[idx].add(a)

            outs = _agg_outputs(
                jnp,
                agg_sig,
                values,
                valid,
                sum_of=sum_of,
                min_of=lambda a: (
                    jnp.full(buckets, _max_of(jnp, a.dtype), dtype=a.dtype)
                    .at[idx]
                    .min(a)
                ),
                max_of=lambda a: (
                    jnp.full(buckets, _min_of(jnp, a.dtype), dtype=a.dtype)
                    .at[idx]
                    .max(a)
                ),
                count_all=present,
                merge_ops={
                    "sum": lambda t: collectives.psum(t, ROW_AXIS),
                    "min": lambda t: collectives.pmin(t, ROW_AXIS),
                    "max": lambda t: collectives.pmax(t, ROW_AXIS),
                },
            )
            return (present,) + tuple(outs)

        n_out = 1 + len(agg_sig)
        mapped = shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(ROW_AXIS), P()) + tuple(P(ROW_AXIS) for _ in range(num_vals + 1)),
            out_specs=tuple(P() for _ in range(n_out)),
        )
        _COMPILE_CACHE[cache_key] = jax.jit(mapped)
    return _COMPILE_CACHE[cache_key]


def _dedupe_cols(
    agg_cols: Sequence[Tuple[Any, ...]]
) -> Tuple[Tuple[Tuple[str, str, int, bool], ...], List[Any]]:
    """Dedupe value arrays by identity → (specs with column indexes, arrays).

    ``agg_cols`` entries are ``(name, agg, arr)`` or ``(name, agg, arr,
    nullable)``; the same array referenced by several aggs (avg → sum+count)
    is passed to the kernel once.
    """
    uniq: Dict[int, int] = {}
    arrays: List[Any] = []
    specs: List[Tuple[str, str, int, bool]] = []
    for entry in agg_cols:
        name, agg, arr = entry[0], entry[1], entry[2]
        nullable = bool(entry[3]) if len(entry) > 3 else True
        if id(arr) not in uniq:
            uniq[id(arr)] = len(arrays)
            arrays.append(arr)
        specs.append((name, agg, uniq[id(arr)], nullable))
    return tuple(specs), arrays


def dense_buckets(rng: int) -> int:
    """Bucket count for a dense plan over a key range of ``rng`` distinct
    slots: the next power of two STRICTLY greater than ``rng``, so the
    top bucket is free for padding/invalid rows (real keys occupy
    ``[0, rng)`` and never reach it); pow2 bounds compiled variants."""
    return 1 << rng.bit_length()


def dense_kernel_parts(
    mesh: Any, agg_cols: List[Tuple[Any, ...]], buckets: int
) -> "Tuple[Any, List[Any], Tuple[Tuple[str, str, int, bool], ...]]":
    """The callable + deduped value arrays + signature of the dense-bucket
    kernel — exposed so callers can compose the kernel with further device
    work inside ONE jitted program."""
    agg_sig, arrays = _dedupe_cols(agg_cols)
    return _get_compiled_dense(mesh, buckets, agg_sig), arrays, agg_sig


def device_dense_groupby(
    mesh: Any,
    key_arr: Any,
    agg_cols: List[Tuple[Any, ...]],
    valid: Any,
    kmin: int,
    buckets: int,
) -> "Tuple[Any, List[Tuple[str, Any]]]":
    """Dense-bucket groupby that STAYS on device.

    Returns ``(present, [(name, array), ...])`` — per-bucket presence
    counts and aggregate tables, cross-shard merged and replicated, with
    NaN marking NULL (all-NULL groups). No host transfer happens here;
    callers either fetch (``_dense_groupby_partials``) or finish the
    result on device (the engine's device-resident aggregate)."""
    import numpy as np_

    compiled, arrays, agg_sig = dense_kernel_parts(mesh, agg_cols, buckets)
    outs = compiled(key_arr, np_.int64(kmin), *arrays, valid)
    return outs[0], [(spec[0], arr) for spec, arr in zip(agg_sig, outs[1:])]


def _dense_groupby_partials(
    mesh: Any,
    key_name: str,
    key_arr: Any,
    agg_cols: List[Tuple[Any, ...]],
    valid: Any,
    kmin: int,
    buckets: int,
) -> "Any":
    import jax
    import numpy as np_
    import pandas as pd

    present_a, named = device_dense_groupby(
        mesh, key_arr, agg_cols, valid, kmin, buckets
    )
    outs = [present_a] + [a for _, a in named]
    agg_sig = [(n,) for n, _ in named]
    # outputs are cross-shard merged + replicated: ONE table comes to host.
    # Start every copy before reading any, so the transfers overlap.
    for o in outs:
        o.copy_to_host_async()
    host = [np_.asarray(jax.device_get(o)) for o in outs]
    present = host[0]
    # the overflow bucket (buckets-1) may mix padding rows; presence counts
    # only valid rows, so zero-presence buckets drop out naturally
    (idx,) = np_.nonzero(present > 0)
    data: Dict[str, Any] = {key_name: idx.astype(np_.int64) + kmin}
    for spec, arr in zip(agg_sig, host[1:]):
        data[spec[0]] = arr[idx]
    return pd.DataFrame(data)


class PartialsTooLarge(Exception):
    """The per-shard group count is too high for the O(shards × groups)
    host transfer — callers should fall back to a host-side plan."""


def device_groupby_partials(
    mesh: Any,
    key_cols: Dict[str, Any],
    agg_cols: List[Tuple[Any, ...]],
    valid_mask: Any,
    max_partial_rows: Optional[int] = None,
    range_hint: Optional[Tuple[int, int]] = None,
) -> "Any":
    """Run the device phase; return a host pandas frame of per-shard-group
    partials. Strategy: single int key with a small range → dense scatter-add
    (no sort); otherwise lexicographic sort + segment reduction. Only
    ``O(shards * groups)`` rows are transferred either way.

    ``agg_cols`` entries are ``(name, agg, arr)`` or ``(name, agg, arr,
    nullable)`` — ``nullable=False`` marks a float column proved NaN-free,
    which skips the NaN-as-NULL masking work in the kernels.
    ``range_hint`` is the caller's cached (min, max) of the single key
    column (``JaxDataFrame.key_range``) — it skips the device probe AND its
    device→host roundtrip.
    """
    import jax
    import numpy as np_
    import pandas as pd

    from ..parallel.mesh import ROW_AXIS

    key_names = list(key_cols.keys())
    valid0 = valid_mask
    if len(key_names) == 1:
        import jax.numpy as jnp

        karr = key_cols[key_names[0]]
        if jnp.issubdtype(karr.dtype, jnp.integer):
            if range_hint is not None:
                kmin, kmax = range_hint
            else:
                kmin_a, kmax_a = _get_compiled_minmax(mesh)(karr, valid0)
                kmin_a.copy_to_host_async()
                kmax_a.copy_to_host_async()
                kmin = int(np_.asarray(jax.device_get(kmin_a))[0])
                kmax = int(np_.asarray(jax.device_get(kmax_a))[0])
            rng = kmax - kmin + 1
            if 0 < rng <= _DENSE_MAX_RANGE:
                buckets = dense_buckets(rng)
                return _dense_groupby_partials(
                    mesh, key_names[0], karr, agg_cols, valid0, kmin, buckets
                )
    agg_sig, arrays = _dedupe_cols(agg_cols)
    compiled = _get_compiled_kernel(mesh, len(key_names), agg_sig)
    valid = valid0
    in_args = tuple(key_cols.values()) + tuple(arrays) + (valid,)
    outs = compiled(*in_args)
    nsegs = np_.asarray(jax.device_get(outs[0]))  # (shards,) tiny transfer
    shards = mesh.shape[ROW_AXIS]
    if max_partial_rows is not None and int(nsegs.sum()) > max_partial_rows:
        # cardinality guard: shipping this many partial rows would beat the
        # purpose of the bounded-transfer design
        raise PartialsTooLarge(
            f"{int(nsegs.sum())} partial rows > limit {max_partial_rows}"
        )
    k_max = int(nsegs.max()) if len(nsegs) > 0 else 0
    if k_max == 0:
        return pd.DataFrame(
            {n: [] for n in key_names + [s[0] for s in agg_sig]}
        )
    # round up to limit distinct compiled slicers
    k = 1 << (k_max - 1).bit_length()
    local_n = outs[1].shape[0] // shards
    k = min(k, local_n)
    sliced = _get_compiled_slicer(mesh, len(outs) - 1, k)(*outs[1:])
    for a in sliced:
        a.copy_to_host_async()
    host = [np_.asarray(jax.device_get(a)).reshape(shards, k) for a in sliced]
    # keep only the first nsegs[s] rows of each shard block
    take = np_.arange(k)[None, :] < nsegs[:, None]
    srow, idx = np_.nonzero(take)
    data = {}
    for name, arr in zip(key_names, host[: len(key_names)]):
        data[name] = arr[srow, idx]
    for spec, arr in zip(agg_sig, host[len(key_names) :]):
        data[spec[0]] = arr[srow, idx]
    return pd.DataFrame(data)


def merge_partials(
    partials: "Any", key_names: List[str], agg_specs: List[Tuple[str, str]]
) -> "Any":
    """Host phase: combine per-shard partials into final aggregates.

    NaN partials mean "this shard's group slice was all-NULL" — min/max use
    pandas' skipna merge, and sum uses ``min_count=1`` so a group that is
    all-NULL across every shard stays NULL instead of becoming 0.
    """

    sum_cols: List[str] = []
    agg_map: Dict[str, Any] = {}
    for name, agg in agg_specs:
        if agg == "sum":
            sum_cols.append(name)
        elif agg == "count":
            agg_map[name] = "sum"
        elif agg in ("min", "max"):
            agg_map[name] = agg
        else:  # pragma: no cover
            raise NotImplementedError(agg)
    grouped = partials.groupby(key_names, dropna=False, sort=False)
    pieces = []
    if len(sum_cols) > 0:
        # vectorized (no per-group python) NULL-preserving sum
        pieces.append(grouped[sum_cols].sum(min_count=1))
    if len(agg_map) > 0:
        pieces.append(grouped.agg(agg_map))
    merged = pieces[0] if len(pieces) == 1 else pieces[0].join(pieces[1])
    # restore the caller's column order
    return merged[[n for n, _ in agg_specs]].reset_index()
