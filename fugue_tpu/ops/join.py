"""Device joins: broadcast and shuffle hash joins over the mesh.

The reference delegates joins to backend SQL engines / task shuffles
(SURVEY §2.9, ``fugue_duckdb/execution_engine.py:233+``); here they are
static-shape XLA kernels (SURVEY §7 "mask, don't branch"):

- keys (one or many, int/float/bool) are mixed into a u64 row hash; the
  right side is sorted by hash, the left probes with ``searchsorted``
  (O(n log m) on the VPU) and verifies REAL key equality on the gathered
  row, so hash collisions can only cause a fallback (duplicate hashes on
  the right are detected at prep), never a wrong match;
- join types map onto the frame validity mask: ``inner``/``semi`` AND the
  match in, ``anti`` ANDs its negation, ``left_outer`` keeps all left rows
  and NaN-fills gathered values (device NULL) — so no join ever compacts
  or materializes variable-shape output;
- strategies: **broadcast** replicates a small right side to every device;
  **shuffle** co-partitions both sides by key hash with the all-to-all
  exchange (``ops/shuffle.py``) and probes shard-locally — the large×large
  path. Both require unique join keys on the right (verified on device);
  many-to-many joins fall back to the host engine.

NULL keys never match (SQL semantics): NaN float keys are excluded from
both sides' match sets on device.
"""

from typing import Any, Dict, List, Optional, Tuple

from ..parallel.mesh import ROW_AXIS, num_row_shards
from . import collectives
from .shuffle import _hash_cols
from jax import shard_map

_JOIN_CACHE: Dict[Any, Any] = {}

# right sides larger than this use the shuffle strategy
MAX_BROADCAST_ROWS = 1 << 20
# per-shard output-slot budget for the 1:N expansion join
MAX_EXPAND_ROWS = 1 << 22


def _key_hash_and_valid(jnp: Any, key_cols: List[Any], valid: Any):
    """(u64 hash, validity excluding NaN keys) for a set of key columns."""
    kv = valid
    for c in key_cols:
        if jnp.issubdtype(c.dtype, jnp.floating):
            kv = kv & ~jnp.isnan(c)
    return _hash_cols(jnp, key_cols), kv


def _probe_body(
    jnp: Any,
    how: str,
    fk_cols: Tuple[Any, ...],
    f_valid: Any,
    rk_sorted_hash: Any,
    r_order: Any,
    r_nvalid: Any,
    rk_cols: Tuple[Any, ...],
    r_values: Tuple[Any, ...],
    fills: Tuple[Any, ...] = (),
):
    """Shared probe: fact hashes against the hash-sorted right side.

    ``fills`` (static, one per value array) are the left_outer miss values:
    NaN for floats, −1 for dictionary codes, True for null masks, 0 for
    plain ints whose misses get a generated null mask from the returned
    match flags.
    """
    fh, fkv = _key_hash_and_valid(jnp, list(fk_cols), f_valid)
    idx = jnp.searchsorted(rk_sorted_hash, fh)
    idx_c = jnp.clip(idx, 0, rk_sorted_hash.shape[0] - 1)
    cand = (rk_sorted_hash[idx_c] == fh) & (idx < r_nvalid) & fkv
    src = r_order[idx_c]
    # verify true key equality on the candidate row (collision safety)
    eq = cand
    for fk, rk in zip(fk_cols, rk_cols):
        eq = eq & (rk[src] == fk)
    if how == "inner":
        new_valid = f_valid & eq
        gathered = tuple(rv[src] for rv in r_values)
    elif how == "left_outer":
        new_valid = f_valid
        gathered = tuple(
            jnp.where(eq, rv[src], jnp.asarray(fill, dtype=rv.dtype))
            for rv, fill in zip(r_values, fills)
        ) + (eq,)  # match flags: the engine derives generated null masks
    elif how == "semi":
        new_valid = f_valid & eq
        gathered = ()
    elif how == "anti":
        new_valid = f_valid & ~eq
        gathered = ()
    else:  # pragma: no cover
        raise NotImplementedError(how)
    return (new_valid,) + gathered


def _get_compiled_right_prep(mesh: Any, n_keys: int, dtypes: Any, local: bool):
    """Hash + sort the right side; report duplicate hashes among valid rows.

    ``local=True`` preps each shard's block independently (shuffle join);
    ``local=False`` preps a replicated array (broadcast join).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    key = ("rprep", mesh, n_keys, dtypes, local)
    if key not in _JOIN_CACHE:

        def prep(valid: Any, *key_cols: Any):
            h, kv = _key_hash_and_valid(jnp, list(key_cols), valid)
            n = h.shape[0]
            inv = jnp.logical_not(kv)
            iota = lax.iota(jnp.int32, n)
            s_inv, s_h, order = lax.sort((inv, h, iota), num_keys=2)
            nv = kv.sum(dtype=jnp.int64)
            dup = jnp.any(
                (s_h[1:] == s_h[:-1])
                & jnp.logical_not(s_inv[1:])
                & jnp.logical_not(s_inv[:-1])
            )
            # invalid rows sit at the tail but keep arbitrary hashes — pin
            # them to the max so the array stays globally sorted for
            # searchsorted (the idx < nv guard keeps them unmatchable)
            s_h = jnp.where(s_inv, jnp.uint64(0xFFFFFFFFFFFFFFFF), s_h)
            return s_h, order, nv[None], dup[None]

        if local:
            spec = P(ROW_AXIS)
            _JOIN_CACHE[key] = jax.jit(
                shard_map(
                    prep,
                    mesh=mesh,
                    in_specs=tuple(spec for _ in range(1 + n_keys)),
                    out_specs=(spec, spec, spec, spec),
                )
            )
        else:
            _JOIN_CACHE[key] = jax.jit(prep)
    return _JOIN_CACHE[key]


def _get_compiled_probe(
    mesh: Any,
    how: str,
    n_keys: int,
    n_values: int,
    dtypes: Any,
    local: bool,
    fills: Tuple[Any, ...] = (),
):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    key = ("probe", mesh, how, n_keys, n_values, dtypes, local, fills)
    if key not in _JOIN_CACHE:

        def probe(*args: Any):
            (f_valid, s_h, order, nv) = args[:4]
            fk = args[4 : 4 + n_keys]
            rk = args[4 + n_keys : 4 + 2 * n_keys]
            rv = args[4 + 2 * n_keys :]

            def shard_fn(fv_, sh_, od_, nv_, *rest: Any):
                fk_ = rest[:n_keys]
                rk_ = rest[n_keys : 2 * n_keys]
                rv_ = rest[2 * n_keys :]
                return _probe_body(
                    jnp, how, fk_, fv_, sh_, od_, nv_[0], rk_, rv_, fills
                )

            row = P(ROW_AXIS)
            right = row if local else P()
            n_out = 1 + (
                (n_values + 1) if how == "left_outer" else (n_values if how == "inner" else 0)
            )
            return shard_map(
                shard_fn,
                mesh=mesh,
                in_specs=(row, right, right, right)
                + tuple(row for _ in range(n_keys))
                + tuple(right for _ in range(n_keys + n_values)),
                out_specs=tuple(row for _ in range(n_out)),
            )(f_valid, s_h, order, nv, *fk, *rk, *rv)

        _JOIN_CACHE[key] = jax.jit(probe)
    return _JOIN_CACHE[key]


def copartition_by_keys(
    mesh: Any,
    left_cols: Dict[str, Any],
    left_valid: Any,
    left_key_names: List[str],
    right_keys: List[Any],
    right_values: List[Tuple[str, Any, Any]],
    right_valid: Any,
) -> Tuple[Dict[str, Any], Any, List[Any], List[Tuple[str, Any, Any]], Any]:
    """Co-partition both join sides by key hash (ONE all-to-all per side);
    shared by the unique-probe and expansion joins so a dup-key fallback
    never repeats the exchange."""
    from .shuffle import compute_dest, exchange_rows

    n_keys = len(left_key_names)
    l_dest = compute_dest(
        mesh, "hash", [left_cols[k] for k in left_key_names], left_valid
    )
    r_dest = compute_dest(mesh, "hash", list(right_keys), right_valid)
    left_cols, left_valid, _ = exchange_rows(
        mesh, dict(left_cols), left_valid, l_dest
    )
    r_payload = {f"__k{i}__": a for i, a in enumerate(right_keys)}
    r_payload.update({f"__v__{n}": a for n, a, _ in right_values})
    r_payload, right_valid, _ = exchange_rows(
        mesh, r_payload, right_valid, r_dest
    )
    right_keys = [r_payload[f"__k{i}__"] for i in range(n_keys)]
    right_values = [
        (n, r_payload[f"__v__{n}"], f) for n, _, f in right_values
    ]
    return left_cols, left_valid, right_keys, right_values, right_valid


def device_hash_join(
    mesh: Any,
    how: str,
    left_cols: Dict[str, Any],
    left_valid: Any,
    left_key_names: List[str],
    right_keys: List[Any],
    right_valid: Any,
    right_values: List[Tuple[str, Any, Any]],
    strategy: str = "broadcast",
) -> Optional[Tuple[Dict[str, Any], Any, Optional[Any]]]:
    """Join the left payload against prepared right-side arrays.

    - ``left_cols`` is the FULL left payload (columns, null masks, prepared
      probe keys — any row-aligned arrays); ``left_key_names`` picks the
      probe keys out of it;
    - ``right_keys`` are the prepared right key arrays (dictionary codes
      remapped, masked keys as NaN float views — the caller aligns
      representations across frames);
    - ``right_values`` entries are ``(out_name, array, miss_fill)`` — the
      fill is the left_outer NULL for that array's representation (NaN /
      −1 code / True mask / 0 plain).

    Returns ``(new_cols, new_valid, match)`` where ``match`` (left_outer
    only) flags rows that found a partner — the caller derives generated
    null masks for plain columns from it. None → host fallback (non-unique
    right keys / hash collision).

    ``strategy="broadcast"`` expects the right arrays replicated;
    ``"shuffle"`` expects both sides row-sharded and co-partitions them by
    key hash with the all-to-all exchange first.
    """
    import jax
    import numpy as np

    if strategy == "shuffle":
        left_cols, left_valid, right_keys, right_values, right_valid = (
            copartition_by_keys(
                mesh, left_cols, left_valid, left_key_names,
                right_keys, right_values, right_valid,
            )
        )
        strategy = "local"
    shuffle = strategy == "local"
    n_keys = len(left_key_names)
    kdt = tuple(str(a.dtype) for a in right_keys)
    prep = _get_compiled_right_prep(mesh, n_keys, kdt, local=shuffle)
    s_h, order, nv, dup = prep(right_valid, *right_keys)
    if bool(np.asarray(jax.device_get(dup)).any()):
        return None  # duplicate keys (or hash collision) → host join
    vdt = tuple(str(a.dtype) for _, a, _ in right_values)
    fills = (
        tuple(f for _, _, f in right_values) if how == "left_outer" else ()
    )
    probe = _get_compiled_probe(
        mesh,
        how,
        n_keys,
        len(right_values),
        (kdt, vdt),
        local=shuffle,
        fills=fills,
    )
    outs = probe(
        left_valid,
        s_h,
        order,
        nv,
        *[left_cols[k] for k in left_key_names],
        *right_keys,
        *[a for _, a, _ in right_values],
    )
    new_valid = outs[0]
    match = None
    new_cols = dict(left_cols)
    if how == "inner":
        for (name, _, _), arr in zip(right_values, outs[1:]):
            new_cols[name] = arr
    elif how == "left_outer":
        for (name, _, _), arr in zip(right_values, outs[1:-1]):
            new_cols[name] = arr
        match = outs[-1]
    return new_cols, new_valid, match


def _get_compiled_expand_count(mesh: Any, n_keys: int, dtypes: Any, local: bool, miss_slot: bool):
    """Phase A of the 1:N expansion: per-left-row candidate counts (hash-run
    length in the sorted right side), exclusive offsets, and the replicated
    per-shard max slot total (→ static output capacity)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    key = ("xcount", mesh, n_keys, dtypes, local, miss_slot)
    if key not in _JOIN_CACHE:

        def count(f_valid: Any, s_h: Any, nv: Any, *fk: Any):
            fh, fkv = _key_hash_and_valid(jnp, list(fk), f_valid)
            lo = jnp.searchsorted(s_h, fh, side="left")
            hi = jnp.searchsorted(s_h, fh, side="right")
            hi = jnp.minimum(hi, nv[0])
            lo = jnp.minimum(lo, hi)
            cand = jnp.where(f_valid & fkv, hi - lo, 0).astype(jnp.int64)
            slots = cand + (f_valid.astype(jnp.int64) if miss_slot else 0)
            off = jnp.cumsum(slots) - slots  # exclusive
            total = jnp.where(
                slots.shape[0] > 0, off[-1] + slots[-1], jnp.int64(0)
            )
            return cand, lo.astype(jnp.int64), off, collectives.pmax(total, ROW_AXIS)[None]

        row = P(ROW_AXIS)
        right = row if local else P()
        _JOIN_CACHE[key] = jax.jit(
            shard_map(
                count,
                mesh=mesh,
                in_specs=(row, right, right) + tuple(row for _ in range(n_keys)),
                out_specs=(row, row, row, P()),
            )
        )
    return _JOIN_CACHE[key]


def _get_compiled_expand(
    mesh: Any,
    how: str,
    cap: int,
    n_keys: int,
    n_left: int,
    n_values: int,
    dtypes: Any,
    local: bool,
    fills: Tuple[Any, ...],
):
    """Phase B: materialize one output row per (left row, candidate) pair
    into a static ``cap``-per-shard buffer; collisions and misses become
    masked slots, never wrong rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    key = ("xpand", mesh, how, cap, n_keys, n_left, n_values, dtypes, local, fills)
    if key not in _JOIN_CACHE:

        def expand(*args: Any):
            cand, lo, off, f_valid, order = args[:5]
            fk = args[5 : 5 + n_keys]
            lp = args[5 + n_keys : 5 + n_keys + n_left]
            rk = args[5 + n_keys + n_left : 5 + 2 * n_keys + n_left]
            rv = args[5 + 2 * n_keys + n_left :]
            n = f_valid.shape[0]
            nr = order.shape[0]
            io = lax.iota(jnp.int64, cap)
            row = jnp.clip(
                jnp.searchsorted(off, io, side="right") - 1, 0, n - 1
            )
            within = io - off[row]
            is_cand = within < cand[row]
            src = order[jnp.clip(lo[row] + within, 0, nr - 1)]
            eq = is_cand & f_valid[row]
            for k_, r_ in zip(fk, rk):
                eq = eq & (r_[src] == k_[row])
            matched = (
                jnp.zeros(n, dtype=jnp.int32)
                .at[row]
                .max(eq.astype(jnp.int32), mode="drop")
            ) > 0
            if how in ("semi", "anti"):
                mres = matched if how == "semi" else jnp.logical_not(matched)
                return (f_valid & mres,)
            total = off[-1] + cand[-1] + (
                f_valid[-1].astype(jnp.int64) if how == "left_outer" else 0
            )
            in_range = io < total
            if how == "left_outer":
                miss = (
                    (within == cand[row])
                    & f_valid[row]
                    & jnp.logical_not(matched[row])
                )
                valid_out = in_range & (eq | miss)
            else:
                valid_out = in_range & eq
            louts = tuple(a[row] for a in lp)
            if how == "left_outer":
                routs = tuple(
                    jnp.where(eq, a[src], jnp.asarray(f, dtype=a.dtype))
                    for a, (f,) in zip(rv, fills_z)
                )
            else:
                routs = tuple(a[src] for a in rv)
            return (valid_out,) + louts + routs + ((eq,) if how == "left_outer" else ())

        fills_z = [(f,) for f in fills] if len(fills) else [(0,)] * n_values
        row_spec = P(ROW_AXIS)
        right = row_spec if local else P()
        n_out = (
            1
            if how in ("semi", "anti")
            else 1 + n_left + n_values + (1 if how == "left_outer" else 0)
        )
        _JOIN_CACHE[key] = jax.jit(
            shard_map(
                expand,
                mesh=mesh,
                in_specs=(row_spec, row_spec, row_spec, row_spec, right)
                + tuple(row_spec for _ in range(n_keys + n_left))
                + tuple(right for _ in range(n_keys + n_values)),
                out_specs=tuple(row_spec for _ in range(n_out)),
            )
        )
    return _JOIN_CACHE[key]


def device_expand_join(
    mesh: Any,
    how: str,
    left_cols: Dict[str, Any],
    left_valid: Any,
    left_key_names: List[str],
    right_keys: List[Any],
    right_valid: Any,
    right_values: List[Tuple[str, Any, Any]],
    strategy: str = "broadcast",
) -> Optional[Tuple[Dict[str, Any], Any, Optional[Any]]]:
    """1:N / N:M device join — duplicate right keys allowed.

    Same contract as :func:`device_hash_join` but the output is an
    EXPANDED frame: one row per (left row, matching right row), built in a
    statically-capacity-negotiated buffer (the only host sync is the tiny
    replicated slot-total). For ``semi``/``anti`` the left frame keeps its
    shape and only the validity mask changes.

    The reference handles 1:N joins on every backend via its SQL engines
    (``fugue_test/execution_suite.py:379-544``); this is the device-native
    equivalent.
    """
    import jax
    import numpy as np

    if strategy == "shuffle":
        left_cols, left_valid, right_keys, right_values, right_valid = (
            copartition_by_keys(
                mesh, left_cols, left_valid, left_key_names,
                right_keys, right_values, right_valid,
            )
        )
        strategy = "local"
    shuffle = strategy == "local"
    n_keys = len(left_key_names)
    kdt = tuple(str(a.dtype) for a in right_keys)
    prep = _get_compiled_right_prep(mesh, n_keys, kdt, local=shuffle)
    s_h, order, nv, _dup = prep(right_valid, *right_keys)
    fk_arrs = [left_cols[k] for k in left_key_names]
    counter = _get_compiled_expand_count(
        mesh, n_keys, kdt, local=shuffle, miss_slot=(how == "left_outer")
    )
    cand, lo, off, max_total = counter(left_valid, s_h, nv, *fk_arrs)
    mt = int(np.asarray(jax.device_get(max_total))[0])
    if mt > MAX_EXPAND_ROWS:
        return None  # output would blow past the per-shard budget → host
    cap = 1 << (max(1, mt) - 1).bit_length()  # pow2 ≥ mt, ≥ 1
    left_payload_names = [k for k in left_cols if k not in left_key_names]
    vdt = tuple(str(a.dtype) for _, a, _ in right_values)
    ldt = tuple(str(left_cols[k].dtype) for k in left_payload_names)
    fills = (
        tuple(f for _, _, f in right_values) if how == "left_outer" else ()
    )
    expander = _get_compiled_expand(
        mesh,
        how,
        cap,
        n_keys,
        len(left_payload_names),
        len(right_values),
        (kdt, ldt, vdt),
        local=shuffle,
        fills=fills,
    )
    outs = expander(
        cand,
        lo,
        off,
        left_valid,
        order,
        *fk_arrs,
        *[left_cols[k] for k in left_payload_names],
        *right_keys,
        *[a for _, a, _ in right_values],
    )
    if how in ("semi", "anti"):
        return dict(left_cols), outs[0], None
    new_valid = outs[0]
    new_cols: Dict[str, Any] = {}
    lo_i = 1
    for k, arr in zip(left_payload_names, outs[lo_i : lo_i + len(left_payload_names)]):
        new_cols[k] = arr
    vi = lo_i + len(left_payload_names)
    for (name, _, _), arr in zip(right_values, outs[vi : vi + len(right_values)]):
        new_cols[name] = arr
    match = outs[-1] if how == "left_outer" else None
    return new_cols, new_valid, match


def device_broadcast_inner_join(
    mesh: Any,
    fact_cols: Dict[str, Any],
    fact_valid: Any,
    key_name: str,
    dim_cols: Dict[str, Any],
    dim_valid: Any,
) -> Any:
    """Back-compat single-key INNER wrapper over :func:`device_hash_join`."""
    import math

    values = [
        (n, a, math.nan) for n, a in dim_cols.items() if n != key_name
    ]
    res = device_hash_join(
        mesh,
        "inner",
        fact_cols,
        fact_valid,
        [key_name],
        [dim_cols[key_name]],
        dim_valid,
        values,
    )
    if res is None:
        return None
    new_cols, new_valid, _ = res
    return new_cols, new_valid
