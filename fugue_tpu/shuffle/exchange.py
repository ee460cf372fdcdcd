"""Device-resident staged exchange — the ``device_exchange`` strategy rung.

Joins whose sides exceed the PER-DEVICE budget but fit AGGREGATE mesh
memory (budget × shards) do not need the spill path's host detour: the
rows are already device-resident, only their *placement* is wrong. This
module moves them with the memory-efficient staged redistribution
schedule of arXiv:2112.01075 — one hop at a time around the mesh ring —
instead of the single-shot ``all_to_all`` the copartition rung uses:

1. destinations come from the same splitmix64 key hash
   (``ops/shuffle.compute_dest``) and the same per-destination rank /
   count negotiation as the in-device exchange, so chain steps and
   bucketing share ONE compiled program family;
2. every shard sorts its rows ONCE by hop distance (stable, so within-
   destination order survives), after which the rows destined ``k``
   shards ahead are a contiguous block and each stage's send buffer is a
   ``cap``-row slice of it — no per-stage O(rows) scatter — where
   ``cap`` is sized so the buffer's bytes stay under the per-stage
   payload cap (``fugue.tpu.shuffle.device_exchange.stage_bytes``,
   default 1/8 of ``fugue.tpu.shuffle.device_budget_bytes``);
3. ONE ``ppermute`` ring shift moves each shard's stage buffer ``k``
   hops forward — peak in-flight collective payload is a single stage
   buffer per device, never the ``shards × cap`` of an all-to-all;
4. received rows compact-append into output buffers sized by the true
   max received total; hops whose block exceeds ``cap`` run multiple
   bounded rounds.

The whole schedule is device-to-device: zero host decode, zero H2D
round trips between partition and join kernel (the acceptance criterion
the spill path's mem tier cannot meet). Spill remains the bit-identical
fallback past aggregate memory or behind the
``fugue.tpu.shuffle.device_exchange.enabled`` kill-switch.
"""

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..parallel.mesh import ROW_AXIS, num_row_shards, row_sharding
from ..ops import collectives
from ..ops.shuffle import (
    _get_compiled_counts,
    _get_compiled_lenmask,
    compute_dest,
)
from jax import shard_map

__all__ = [
    "stage_capacity_rows",
    "staged_exchange_rows",
    "staged_copartition_by_keys",
]

_COMPILE_CACHE: Dict[Any, Any] = {}


def _pow2_ceil(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


def _pow2_floor(n: int) -> int:
    return 1 << (max(1, int(n)).bit_length() - 1)


def _row_bytes(arrays: Dict[str, Any]) -> int:
    """Bytes one row occupies in the stage buffers: every payload array's
    itemsize plus the validity bool that travels with it."""
    return 1 + sum(np.dtype(a.dtype).itemsize for a in arrays.values())


def stage_capacity_rows(stage_bytes: int, row_bytes: int) -> int:
    """Stage-buffer row capacity under the per-stage byte cap, rounded
    DOWN to a pow2 (rounding up could overshoot the budget; rounding down
    keeps compiled variants reusable AND the payload provably bounded)."""
    return _pow2_floor(max(1, int(stage_bytes) // max(1, int(row_bytes))))


# fused-schedule unroll ceiling: shards × rounds stages trace into ONE
# program below this, so the whole schedule costs a single dispatch; past
# it (tiny stage caps on big meshes) compile time would balloon, and the
# per-stage dispatch loop takes over
_MAX_FUSED_STAGES = 64


def _sorted_prep(shards: int, cap: int, dest: Any, valid: Any, arrs: Any):
    """Sort a shard's rows ONCE by hop distance — stable, so within-
    destination order (the rank) survives — turning every stage's send
    block into a contiguous slice. The per-stage alternative (scatter the
    window's rows into the stage buffer) costs O(rows) EVERY stage; with
    rows >> cap that scatter dominated the whole schedule. Invalid rows
    sort past every real hop; the sorted arrays are padded by ``cap``
    rows so a window starting at the block tail never clamps back into
    live rows. Returns the hop block offsets (``shards + 1`` entries:
    ``offs[k]`` = first sorted position with hop ``k``) plus the sorted,
    padded arrays. Shared by the fused schedule and the per-stage prep
    kernel so the two dispatch modes can never drift."""
    import jax.numpy as jnp
    from jax import lax

    n = dest.shape[0]
    me = lax.axis_index(ROW_AXIS)
    hop = lax.rem(
        dest.astype(jnp.int32) - me + np.int32(shards), np.int32(shards)
    )
    big_hop = jnp.where(valid, hop, np.int32(shards))
    iota = lax.iota(jnp.int32, n)
    sorted_hop, perm = lax.sort((big_hop, iota), num_keys=1)
    counts = jnp.zeros(shards + 1, dtype=jnp.int32).at[sorted_hop].add(1)
    offs = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32), jnp.cumsum(counts[:shards])]
    )
    pad = [
        jnp.concatenate([a[perm], jnp.zeros(cap, dtype=a.dtype)])
        for a in arrs
    ]
    return offs, pad


def _stage_body(
    k: int,
    lo: Any,
    cap: int,
    out_cap: int,
    offs: Any,
    sarrs: Any,
    out_len: Any,
    bufs: Any,
) -> Tuple[Any, list]:
    """ONE stage of the staged schedule: the ``[lo, lo+cap)`` window of
    the hop-``k`` block (rows pre-sorted by ``_sorted_prep``, so the
    window is ONE ``dynamic_slice``), ONE ``ppermute`` ring shift
    delivers it, and received rows compact-append into the output
    buffers. Peak collective payload = one stage buffer (``cap`` rows),
    independent of both skew and shard count; ``k == 0`` is the local hop
    (no comm). Shared by the per-stage kernel and the fused schedule so
    the two dispatch modes can never drift."""
    import jax.numpy as jnp
    from jax import lax

    start = offs[k] + lo
    cnt = jnp.clip(offs[k + 1] - start, 0, np.int32(cap))
    send_valid = lax.iota(jnp.int32, cap) < cnt
    # pack the stage into ONE contiguous byte payload — the validity lane
    # plus every array's window slice bitcast to bytes — so each stage is
    # one collective. Per-collective sync dominates a stage on mesh
    # backends; per-array ppermutes multiplied that by the column count.
    # float64 columns travel in a second payload of their own: v5e
    # emulates float64 and cannot bitcast it. The payloads total
    # cap × row_bytes: the exact quantity ``stage_capacity_rows`` budgets
    # and ``peak_exchange`` records.
    lanes = [send_valid.astype(jnp.uint8)]
    doubles = []
    for a in sarrs:
        send = lax.dynamic_slice_in_dim(a, start, cap)
        if a.dtype == jnp.float64:
            doubles.append(send)
        elif np.dtype(a.dtype).itemsize == 1:
            lanes.append(send.astype(jnp.uint8))
        else:
            lanes.append(lax.bitcast_convert_type(send, jnp.uint8).reshape(-1))
    recv = collectives.ppermute(jnp.concatenate(lanes), ROW_AXIS, k)
    if doubles:
        recv_d = collectives.ppermute(jnp.concatenate(doubles), ROW_AXIS, k)
    recv_valid = recv[:cap].astype(bool)
    cum = jnp.cumsum(recv_valid.astype(jnp.int32))
    pos = out_len[0] + cum - 1
    idx = jnp.where(recv_valid, pos, out_cap)
    new_bufs = []
    off, off_d = cap, 0
    for a, buf in zip(sarrs, bufs):
        itemsize = np.dtype(a.dtype).itemsize
        if a.dtype == jnp.float64:
            got = recv_d[off_d : off_d + cap]
            off_d += cap
        else:
            chunk = recv[off : off + cap * itemsize]
            off += cap * itemsize
            if itemsize == 1:
                got = chunk.astype(a.dtype)
            else:
                got = lax.bitcast_convert_type(
                    chunk.reshape(cap, itemsize), a.dtype
                )
        new_bufs.append(buf.at[idx].set(got, mode="drop"))
    new_len = out_len[0] + cum[-1]
    return new_len[None], new_bufs


def _get_compiled_prep(mesh: Any, dtypes: Tuple[Any, ...], cap: int):
    """Standalone sort-by-hop prep for the per-stage dispatch mode:
    returns the hop block offsets plus the sorted, ``cap``-padded arrays
    the hop kernels slice from. (The fused schedule inlines
    ``_sorted_prep`` instead — one dispatch covers prep AND stages.)"""
    import jax
    from jax.sharding import PartitionSpec as P

    shards = num_row_shards(mesh)
    cache_key = ("xprep", mesh, dtypes, cap)
    if cache_key not in _COMPILE_CACHE:

        def kernel(dest: Any, valid: Any, *arrs: Any):
            offs, pad = _sorted_prep(shards, cap, dest, valid, arrs)
            return (offs,) + tuple(pad)

        row = P(ROW_AXIS)
        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=(row, row) + tuple(row for _ in dtypes),
                out_specs=tuple(row for _ in range(1 + len(dtypes))),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_hop(
    mesh: Any, dtypes: Tuple[Any, ...], cap: int, out_cap: int, k: int
):
    """Per-stage dispatch variant: one jitted program per hop distance,
    round window passed as a replicated scalar, send blocks sliced from
    the ``_get_compiled_prep`` output. Used when the schedule is too long
    to unroll (``> _MAX_FUSED_STAGES`` stages)."""
    import jax
    from jax.sharding import PartitionSpec as P

    cache_key = ("xhop", mesh, dtypes, cap, out_cap, k)
    if cache_key not in _COMPILE_CACHE:

        def kernel(offs: Any, out_len: Any, r: Any, *rest: Any):
            sarrs = rest[: len(dtypes)]
            bufs = rest[len(dtypes) :]
            new_len, new_bufs = _stage_body(
                k, r[0] * cap, cap, out_cap, offs, sarrs, out_len, bufs
            )
            return (new_len,) + tuple(new_bufs)

        row = P(ROW_AXIS)
        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=(row, row, P())
                + tuple(row for _ in range(2 * len(dtypes))),
                out_specs=tuple(row for _ in range(1 + len(dtypes))),
            )
        )
    return _COMPILE_CACHE[cache_key]


def _get_compiled_schedule(
    mesh: Any, dtypes: Tuple[Any, ...], cap: int, out_cap: int, rounds: int
):
    """Fused variant: the WHOLE staged schedule — every hop distance ×
    every round window, unrolled at trace time — as one jitted program,
    so a side's exchange costs a single dispatch instead of
    ``shards × rounds`` (the dominant cost on dispatch-bound meshes). An
    ``optimization_barrier`` seals every stage's full state before the
    next stage's ops, so XLA cannot overlap two stages' collectives — the
    one-stage-buffer in-flight payload bound survives the fusion."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    shards = num_row_shards(mesh)
    cache_key = ("xsched", mesh, dtypes, cap, out_cap, rounds)
    if cache_key not in _COMPILE_CACHE:

        def kernel(dest: Any, valid: Any, out_len: Any, *rest: Any):
            n = len(dtypes)
            offs, sarrs = _sorted_prep(
                shards, cap, dest, valid, rest[:n]
            )
            bufs = list(rest[n:])
            for k in range(shards):
                for r in range(rounds):
                    out_len, bufs = _stage_body(
                        k, np.int32(r * cap), cap, out_cap,
                        offs, sarrs, out_len, bufs,
                    )
                    # seal the stage: every value the next stage reads
                    # passes through the barrier, so none of its sends
                    # can be hoisted before this stage's receives land
                    sealed = lax.optimization_barrier(
                        tuple([out_len] + bufs + sarrs + [offs])
                    )
                    out_len = sealed[0]
                    bufs = list(sealed[1 : 1 + n])
                    sarrs = list(sealed[1 + n : 1 + 2 * n])
                    offs = sealed[1 + 2 * n]
            return (out_len,) + tuple(bufs)

        row = P(ROW_AXIS)
        _COMPILE_CACHE[cache_key] = jax.jit(
            shard_map(
                kernel,
                mesh=mesh,
                in_specs=(row, row, row)
                + tuple(row for _ in range(2 * len(dtypes))),
                out_specs=tuple(row for _ in range(1 + len(dtypes))),
            )
        )
    return _COMPILE_CACHE[cache_key]


def staged_exchange_rows(
    mesh: Any,
    arrays: Dict[str, Any],
    valid: Any,
    dest: Any,
    stage_bytes: int,
    stats: Optional[Any] = None,
) -> Tuple[Dict[str, Any], Any, int]:
    """Move rows to their destination shards with the staged one-hop-at-
    a-time schedule. Same contract as ``ops.shuffle.exchange_rows`` —
    returns ``(new_arrays, new_valid_mask, received_row_count)`` — but
    per-stage collective payload never exceeds ``stage_bytes`` per device
    (the high-water lands on ``stats.device_exchange_peak_stage_bytes``).
    """
    import jax

    shards = num_row_shards(mesh)
    mx, total, mr = jax.device_get(_get_compiled_counts(mesh)(dest, valid))
    need = int(mx[0])
    row_bytes = _row_bytes(arrays)
    cap = min(_pow2_ceil(need), stage_capacity_rows(stage_bytes, row_bytes))
    rounds = max(1, -(-need // cap))  # ceil; 1 even when nothing moves
    out_cap = _pow2_ceil(int(mr[0]))
    dtypes = tuple(str(a.dtype) for a in arrays.values())
    sharding = row_sharding(mesh)
    out_len = jax.device_put(np.zeros(shards, dtype=np.int32), sharding)
    bufs = [
        jax.device_put(np.zeros(shards * out_cap, dtype=a.dtype), sharding)
        for a in arrays.values()
    ]
    if shards * rounds <= _MAX_FUSED_STAGES:
        # one dispatch for the whole schedule (sort-by-hop prep plus
        # hops × rounds unrolled, stage order identical to the loop below)
        outs = _get_compiled_schedule(mesh, dtypes, cap, out_cap, rounds)(
            dest, valid, out_len, *arrays.values(), *bufs
        )
        out_len = outs[0]
        bufs = list(outs[1:])
    else:
        prepped = _get_compiled_prep(mesh, dtypes, cap)(
            dest, valid, *arrays.values()
        )
        offs, sarrs = prepped[0], prepped[1:]
        for k in range(shards):
            step = _get_compiled_hop(mesh, dtypes, cap, out_cap, k)
            for r in range(rounds):
                outs = step(
                    offs,
                    out_len,
                    np.asarray([r], dtype=np.int32),
                    *sarrs,
                    *bufs,
                )
                out_len = outs[0]
                bufs = list(outs[1:])
    new_valid = _get_compiled_lenmask(mesh, out_cap)(out_len)
    if stats is not None:
        stats.inc("device_exchange_stages", shards * rounds)
        stats.inc("device_exchange_rows", int(total[0]))
        stats.inc("device_exchange_bytes", int(total[0]) * row_bytes)
        stats.peak_exchange(cap * row_bytes)
    new_arrays = {n: b for n, b in zip(arrays.keys(), bufs)}
    return new_arrays, new_valid, int(total[0])


def staged_copartition_by_keys(
    mesh: Any,
    left_cols: Dict[str, Any],
    left_valid: Any,
    left_key_names: List[str],
    right_keys: List[Any],
    right_values: List[Tuple[str, Any, Any]],
    right_valid: Any,
    stage_bytes: int,
    stats: Optional[Any] = None,
) -> Tuple[Dict[str, Any], Any, List[Any], List[Tuple[str, Any, Any]], Any]:
    """Co-partition both join sides by key hash with the STAGED exchange
    (one schedule per side) — the device_exchange analogue of
    ``ops.join.copartition_by_keys``, shared the same way by the
    unique-probe and expansion joins so a dup-key fallback never repeats
    the exchange."""
    n_keys = len(left_key_names)
    l_dest = compute_dest(
        mesh, "hash", [left_cols[k] for k in left_key_names], left_valid
    )
    r_dest = compute_dest(mesh, "hash", list(right_keys), right_valid)
    left_cols, left_valid, _ = staged_exchange_rows(
        mesh, dict(left_cols), left_valid, l_dest, stage_bytes, stats
    )
    r_payload = {f"__k{i}__": a for i, a in enumerate(right_keys)}
    r_payload.update({f"__v__{n}": a for n, a, _ in right_values})
    r_payload, right_valid, _ = staged_exchange_rows(
        mesh, r_payload, right_valid, r_dest, stage_bytes, stats
    )
    right_keys = [r_payload[f"__k{i}__"] for i in range(n_keys)]
    right_values = [
        (n, r_payload[f"__v__{n}"], f) for n, _, f in right_values
    ]
    return left_cols, left_valid, right_keys, right_values, right_valid
