"""Device shuffle: all-to-all row exchange over the mesh rows axis.

The TPU-native replacement for the reference's per-backend repartition
algorithms (``fugue_spark/_utils/partition.py:15-117`` hash/rand/even and
``fugue_dask/_utils.py:44-123``): instead of a task-graph shuffle, rows move
between shards with ONE ``lax.all_to_all`` collective inside ``shard_map``
— the layout XLA maps onto ICI links.

Protocol (static shapes throughout, SURVEY §7 "mask, don't branch"):

1. every row gets a destination shard (hash of keys / even rank / random);
2. a tiny per-(shard, dest) count matrix comes to host to negotiate a
   static block ``capacity`` (pow2-rounded so compiled variants are reused);
3. the exchange kernel sorts rows by destination, scatters them into a
   ``(shards, capacity)`` send buffer, ``all_to_all``s the buffers, and
   returns the received rows + validity mask.

Skew safety: when a hot destination pushes the block capacity past the
round capacity (the pow2 of a shard's even share of its rows per
destination, never below ``SINGLE_ROUND_MAX_CAPACITY``), the exchange
escalates to MULTIPLE bounded rounds (each moving ≤ that many rows per
destination) that compact-append into output buffers sized by the true max
received total — collective buffers and outputs stay O(data), never
O(shards × hot-key count), and a round count never passes the shard count.
"""

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..parallel.mesh import ROW_AXIS, num_row_shards
from . import collectives
from jax import shard_map

_COMPILE_CACHE: Dict[Any, Any] = {}

# splitmix64 multipliers — the standard 64-bit finalizer mix
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)


def _hash_cols(jnp: Any, cols: List[Any]) -> Any:
    """Combine columns into a well-mixed uint64 row hash (device-side)."""
    h = jnp.zeros(cols[0].shape, dtype=jnp.uint64)
    for c in cols:
        if jnp.issubdtype(c.dtype, jnp.floating):
            # bitcast so equal keys hash equally; normalize -0.0 to +0.0
            c = jnp.where(c == 0, jnp.zeros_like(c), c)
            x = jax_bitcast_u64(jnp, c)
        elif c.dtype == jnp.bool_:
            x = c.astype(jnp.uint64)
        else:
            x = c.astype(jnp.uint64)
        x = (x ^ (x >> 30)) * _MIX1
        x = (x ^ (x >> 27)) * _MIX2
        x = x ^ (x >> 31)
        h = h * np.uint64(31) + x
    return h


def jax_bitcast_u64(jnp: Any, c: Any) -> Any:
    import jax.lax as lax

    if c.dtype == jnp.float64:
        return lax.bitcast_convert_type(c, jnp.uint64)
    return lax.bitcast_convert_type(c.astype(jnp.float64), jnp.uint64)


def _get_compiled_dest_hash(mesh: Any, n_keys: int, dtypes: Tuple[Any, ...]):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    shards = num_row_shards(mesh)
    cache_key = ("dest_hash", mesh, n_keys, dtypes)
    if cache_key not in _COMPILE_CACHE:

        def kernel(*cols: Any):
            h = _hash_cols(jnp, list(cols))
            return (h % np.uint64(shards)).astype(jnp.int32)

        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=tuple(P(ROW_AXIS) for _ in range(n_keys)),
                out_specs=P(ROW_AXIS),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_dest_even(mesh: Any):
    """dest = global rank of the valid row, spread evenly over shards
    (invalid rows keep their shard — they're masked anyway)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    shards = num_row_shards(mesh)
    cache_key = ("dest_even", mesh)
    if cache_key not in _COMPILE_CACHE:

        def kernel(valid: Any):
            local = jnp.cumsum(valid.astype(jnp.int64)) - 1  # local rank
            counts = collectives.all_gather(valid.sum(dtype=jnp.int64), ROW_AXIS)
            me = jax.lax.axis_index(ROW_AXIS)
            offset = jnp.where(
                jax.lax.iota(jnp.int64, shards) < me, counts, 0
            ).sum()
            total = counts.sum()
            rank = local + offset
            # ceil-sized blocks: shard i gets ranks [i*block, (i+1)*block)
            block = jnp.maximum((total + shards - 1) // shards, 1)
            return jnp.clip(rank // block, 0, shards - 1).astype(jnp.int32)

        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel, mesh=mesh, in_specs=(P(ROW_AXIS),), out_specs=P(ROW_AXIS)
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_dest_rand(mesh: Any):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    shards = num_row_shards(mesh)
    cache_key = ("dest_rand", mesh)
    if cache_key not in _COMPILE_CACHE:

        def kernel(template: Any, seed: Any):
            me = jax.lax.axis_index(ROW_AXIS)
            key = jax.random.fold_in(jax.random.PRNGKey(seed[0]), me)
            return jax.random.randint(
                key, template.shape, 0, shards, dtype=jnp.int32
            )

        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=(P(ROW_AXIS), P()),
                out_specs=P(ROW_AXIS),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_dest_single(mesh: Any):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    cache_key = ("dest_single", mesh)
    if cache_key not in _COMPILE_CACHE:
        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                lambda template: jnp.zeros(template.shape, jnp.int32),
                mesh=mesh,
                in_specs=(P(ROW_AXIS),),
                out_specs=P(ROW_AXIS),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_counts(mesh: Any):
    """Destination-histogram summary → (max_count, total) as REPLICATED
    scalars: replication keeps the host read addressable from every process
    on multi-host meshes (a sharded matrix would not be)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    shards = num_row_shards(mesh)
    cache_key = ("shuffle_counts", mesh)
    if cache_key not in _COMPILE_CACHE:

        def kernel(dest: Any, valid: Any):
            h = (
                jnp.zeros(shards, dtype=jnp.int32)
                .at[dest]
                .add(valid.astype(jnp.int32))
            )
            received = collectives.psum(h, ROW_AXIS)  # per-dest totals, replicated
            return (
                collectives.pmax(h.max(), ROW_AXIS)[None],
                collectives.psum(h.sum(), ROW_AXIS)[None],
                received.max()[None],
            )

        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=(P(ROW_AXIS), P(ROW_AXIS)),
                out_specs=(P(), P(), P()),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_exchange(
    mesh: Any, dtypes: Tuple[Any, ...], capacity: int
):
    """The all-to-all exchange for ``len(dtypes)`` row-aligned arrays.

    Per shard: sort rows by destination, scatter each destination's rows
    into its block of a ``(shards, capacity)`` send buffer, exchange
    blocks with ``lax.all_to_all``, return flattened received arrays and
    the received-validity mask. Output local length = shards × capacity.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    shards = num_row_shards(mesh)
    cache_key = ("exchange", mesh, dtypes, capacity)
    if cache_key not in _COMPILE_CACHE:

        def kernel(dest: Any, valid: Any, *arrs: Any):
            n = dest.shape[0]
            big_dest = jnp.where(valid, dest, shards)  # invalid rows last
            iota = lax.iota(jnp.int32, n)
            sd, perm = lax.sort((big_dest, iota), num_keys=1)
            # position of each sorted row within its destination block
            starts_tbl = jnp.zeros(shards + 1, dtype=jnp.int32).at[sd].add(1)
            starts = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.cumsum(starts_tbl[:shards])]
            )
            pos = iota - starts[jnp.clip(sd, 0, shards - 1)]
            ok = (sd < shards) & (pos < capacity)
            flat = jnp.where(
                ok, jnp.clip(sd, 0, shards - 1) * capacity + pos, shards * capacity
            )
            send_valid = (
                jnp.zeros(shards * capacity, dtype=bool)
                .at[flat]
                .set(True, mode="drop")
            )
            recv_valid = collectives.all_to_all(
                send_valid.reshape(shards, capacity),
                ROW_AXIS,
                split_axis=0,
                concat_axis=0,
            ).reshape(-1)
            outs = [recv_valid]
            for a in arrs:
                sa = a[perm]
                send = (
                    jnp.zeros(shards * capacity, dtype=a.dtype)
                    .at[flat]
                    .set(sa, mode="drop")
                )
                outs.append(
                    collectives.all_to_all(
                        send.reshape(shards, capacity),
                        ROW_AXIS,
                        split_axis=0,
                        concat_axis=0,
                    ).reshape(-1)
                )
            return tuple(outs)

        n_in = 2 + len(dtypes)
        n_out = 1 + len(dtypes)
        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=tuple(P(ROW_AXIS) for _ in range(n_in)),
                out_specs=tuple(P(ROW_AXIS) for _ in range(n_out)),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_rank(mesh: Any):
    """Per-row rank among rows of the SAME destination on this shard —
    computed once, reused by every round of the multi-round exchange."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    shards = num_row_shards(mesh)
    cache_key = ("shuffle_rank", mesh)
    if cache_key not in _COMPILE_CACHE:

        def kernel(dest: Any, valid: Any):
            n = dest.shape[0]
            big_dest = jnp.where(valid, dest, shards)
            iota = lax.iota(jnp.int32, n)
            sd, perm = lax.sort((big_dest, iota), num_keys=1)
            starts_tbl = jnp.zeros(shards + 1, dtype=jnp.int32).at[sd].add(1)
            starts = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.cumsum(starts_tbl[:shards])]
            )
            pos = iota - starts[jnp.clip(sd, 0, shards - 1)]
            return jnp.zeros(n, dtype=jnp.int32).at[perm].set(pos)

        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=(P(ROW_AXIS), P(ROW_AXIS)),
                out_specs=P(ROW_AXIS),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_round(
    mesh: Any, dtypes: Tuple[Any, ...], cap: int, out_cap: int
):
    """ONE bounded round of the multi-round exchange: send rows whose
    within-destination rank falls in this round's window (≤ ``cap`` rows
    per destination), then compact-append the received rows into the
    accumulating output buffers. Peak collective buffer = shards × cap,
    independent of skew."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    shards = num_row_shards(mesh)
    cache_key = ("xround", mesh, dtypes, cap, out_cap)
    if cache_key not in _COMPILE_CACHE:

        def kernel(dest: Any, valid: Any, rank: Any, out_len: Any, r: Any, *rest: Any):
            arrs = rest[: len(dtypes)]
            bufs = rest[len(dtypes) :]
            lo = r[0] * cap
            sel = valid & (rank >= lo) & (rank < lo + cap)
            flat = jnp.where(
                sel, dest * cap + (rank - lo), shards * cap
            )
            send_valid = (
                jnp.zeros(shards * cap, dtype=bool)
                .at[flat]
                .set(True, mode="drop")
            )
            recv_valid = collectives.all_to_all(
                send_valid.reshape(shards, cap),
                ROW_AXIS,
                split_axis=0,
                concat_axis=0,
            ).reshape(-1)
            cum = jnp.cumsum(recv_valid.astype(jnp.int32))
            pos = out_len[0] + cum - 1
            idx = jnp.where(recv_valid, pos, out_cap)
            new_bufs = []
            for a, buf in zip(arrs, bufs):
                send = (
                    jnp.zeros(shards * cap, dtype=a.dtype)
                    .at[flat]
                    .set(a, mode="drop")
                )
                recv = collectives.all_to_all(
                    send.reshape(shards, cap),
                    ROW_AXIS,
                    split_axis=0,
                    concat_axis=0,
                ).reshape(-1)
                new_bufs.append(buf.at[idx].set(recv, mode="drop"))
            new_len = out_len[0] + cum[-1]
            return (new_len[None],) + tuple(new_bufs)

        row = P(ROW_AXIS)
        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=(row, row, row, row, P())
                + tuple(row for _ in range(2 * len(dtypes))),
                out_specs=tuple(row for _ in range(1 + len(dtypes))),
            )
        )
    return _COMPILE_CACHE[cache_key]


def compute_dest(
    mesh: Any,
    algo: str,
    key_cols: List[Any],
    valid: Any,
    seed: Optional[int] = None,
) -> Any:
    """Destination shard per row for the given algorithm."""
    import numpy as np_

    if algo == "hash":
        dtypes = tuple(str(c.dtype) for c in key_cols)
        return _get_compiled_dest_hash(mesh, len(key_cols), dtypes)(*key_cols)
    if algo == "even":
        return _get_compiled_dest_even(mesh)(valid)
    if algo == "single":
        # every row to shard 0 — the one-partition layout behind global
        # (no PARTITION BY) window evaluation
        return _get_compiled_dest_single(mesh)(valid)
    if algo == "rand":
        if seed is None:
            seed = int(np_.random.default_rng().integers(0, 2**31 - 1))
        template = valid
        return _get_compiled_dest_rand(mesh)(
            template, np_.asarray([seed], dtype=np_.uint32)
        )
    raise ValueError(f"unknown shuffle algo {algo!r}")


# floor of the round capacity: a (shard, dest) pair needing more rows than
# the round capacity escalates to the bounded multi-round exchange, whose
# peak collective buffer stays shards × the round capacity regardless of
# key skew
SINGLE_ROUND_MAX_CAPACITY = 1 << 17


def exchange_plan(
    max_count: int, local_rows: int, shards: int, limit: Optional[int] = None
) -> Tuple[int, int]:
    """``(block capacity, rounds)`` for an exchange whose largest
    (shard, dest) block holds ``max_count`` rows, on shards of
    ``local_rows`` rows. ``rounds == 1`` is the single all-to-all.

    The round capacity is the pow2 of a shard's even share per destination
    (``local_rows / shards``), never below ``limit``: balanced data moves
    in one round whatever its size, and a skewed exchange runs at most
    ``shards`` rounds, each with a send buffer of at most twice the
    shard's rows. (A fixed capacity made a balanced 25M-row shard take 48
    rounds, each re-reading the whole shard.)"""
    floor = SINGLE_ROUND_MAX_CAPACITY if limit is None else limit
    round_cap = max(_pow2_ceil(floor), _pow2_ceil(-(-local_rows // shards)))
    capacity = _pow2_ceil(max_count)  # pow2 → reuse compiled variants
    if capacity <= round_cap:
        return capacity, 1
    return round_cap, -(-max_count // round_cap)


def _pow2_ceil(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def _get_compiled_lenmask(mesh: Any, out_cap: int):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    cache_key = ("lenmask", mesh, out_cap)
    if cache_key not in _COMPILE_CACHE:

        def kernel(out_len: Any):
            return lax.iota(jnp.int32, out_cap) < out_len[0]

        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=(P(ROW_AXIS),),
                out_specs=P(ROW_AXIS),
            )
        )
    return _COMPILE_CACHE[cache_key]


def exchange_rows(
    mesh: Any,
    arrays: Dict[str, Any],
    valid: Any,
    dest: Any,
    round_capacity: Optional[int] = None,
) -> Tuple[Dict[str, Any], Any, int]:
    """Move rows to their destination shards.

    Returns (new_arrays, new_valid_mask, received_row_count).

    Small/balanced exchanges run in ONE all-to-all with block capacity =
    the max per-(shard, dest) count (output local length shards ×
    capacity). Skewed exchanges — a hot destination pushing the block past
    the round capacity of :func:`exchange_plan` (``round_capacity``
    overrides its floor) — run at most ``shards`` bounded rounds: each
    round moves at most the round capacity per destination and
    compact-appends into output buffers sized by the TRUE max received
    total, so neither the collective buffers nor the output inflate with
    skew.
    """
    import jax
    import numpy as np_

    mx, total, mr = jax.device_get(_get_compiled_counts(mesh)(dest, valid))
    shards = num_row_shards(mesh)
    capacity, rounds = exchange_plan(
        int(mx[0]), dest.shape[0] // shards, shards, round_capacity
    )
    dtypes = tuple(str(a.dtype) for a in arrays.values())
    if rounds == 1:
        compiled = _get_compiled_exchange(mesh, dtypes, capacity)
        outs = compiled(dest, valid, *arrays.values())
        new_valid = outs[0]
        new_arrays = {k: v for k, v in zip(arrays.keys(), outs[1:])}
        return new_arrays, new_valid, int(total[0])
    # ---- multi-round path -------------------------------------------------
    from ..parallel.mesh import row_sharding

    out_cap = _pow2_ceil(int(mr[0]))
    sharding = row_sharding(mesh)
    rank = _get_compiled_rank(mesh)(dest, valid)
    out_len = jax.device_put(
        np_.zeros(shards, dtype=np_.int32), sharding
    )
    bufs = [
        jax.device_put(
            np_.zeros(shards * out_cap, dtype=a.dtype), sharding
        )
        for a in arrays.values()
    ]
    step = _get_compiled_round(mesh, dtypes, capacity, out_cap)
    for r in range(rounds):
        outs = step(
            dest,
            valid,
            rank,
            out_len,
            np_.asarray([r], dtype=np_.int32),
            *arrays.values(),
            *bufs,
        )
        out_len = outs[0]
        bufs = list(outs[1:])
    new_valid = _get_compiled_lenmask(mesh, out_cap)(out_len)
    new_arrays = {k: v for k, v in zip(arrays.keys(), bufs)}
    return new_arrays, new_valid, int(total[0])
