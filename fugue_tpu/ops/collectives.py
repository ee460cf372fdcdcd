"""Collective wrappers: every cross-shard collective in the device kernels
goes through these instead of raw ``lax``.

One rule lives here: the v5e compiler lowers a 64-bit all-reduce only as a
Sum (``lax.pmin`` on int64 or float64 is refused with "Supported lowering
only of Sum all reduce"). A 64-bit min or max therefore gathers the shards'
values with a Sum (each shard contributes a buffer holding only its own
value, so the sum is exact and its result stays replicated) and reduces
them locally; those operands are per-bucket tables or scalars, so the
gather moves little. ``tests/jax_engine/test_tpu_aot_compile.py``
compiles these collectives for a described v5e mesh.

The reference delegates all of this to its backends' transports (Spark
shuffle / Dask comm / Ray object store — SURVEY §5.8); here the XLA
collectives ARE the transport.
"""

from typing import Any

import numpy as np
from jax import lax

__all__ = ["psum", "pmin", "pmax", "all_gather", "all_to_all", "ppermute"]


def psum(x: Any, axis: str) -> Any:
    return lax.psum(x, axis)


def _wide(x: Any) -> bool:
    return np.dtype(x.dtype).itemsize == 8


def _gather_by_sum(x: Any, axis: str) -> Any:
    """Every shard's ``x`` stacked on a new leading axis, replicated."""
    import jax.numpy as jnp

    u64 = x.dtype == jnp.uint64  # the chip sums signed and float only
    bits = lax.bitcast_convert_type(x, jnp.int64) if u64 else x
    buf = jnp.zeros((lax.axis_size(axis),) + x.shape, bits.dtype)
    out = lax.psum(buf.at[lax.axis_index(axis)].set(bits), axis)
    return lax.bitcast_convert_type(out, jnp.uint64) if u64 else out


def pmin(x: Any, axis: str) -> Any:
    if _wide(x):
        return _gather_by_sum(x, axis).min(axis=0)
    return lax.pmin(x, axis)


def pmax(x: Any, axis: str) -> Any:
    if _wide(x):
        return _gather_by_sum(x, axis).max(axis=0)
    return lax.pmax(x, axis)


def all_gather(x: Any, axis: str, *, tiled: bool = False) -> Any:
    return lax.all_gather(x, axis, tiled=tiled)


def ppermute(x: Any, axis: str, shift: int) -> Any:
    """Ring shift: shard i's block lands on shard ``(i + shift) % n`` —
    ONE point-to-point hop per shard, the staged exchange's primitive.
    Peak in-flight payload is a single block (vs ``all_to_all``'s n
    blocks), which is what lets the staged schedule bound per-stage
    bytes. ``shift % n == 0`` is the local hop: no comm at all."""
    n = lax.axis_size(axis)
    if n == 1 or shift % n == 0:
        # the value is already "varying" here, so a plain pass-through is
        # sound: out_specs stay row-sharded
        return x
    return lax.ppermute(x, axis, [(i, (i + shift) % n) for i in range(n)])


def all_to_all(x: Any, axis: str, split_axis: int, concat_axis: int) -> Any:
    """Shard i's ``x[j]`` block lands on shard j (split/concat over the
    leading axis — the only shape the shuffle kernels use)."""
    assert split_axis == 0 and concat_axis == 0
    if lax.axis_size(axis) == 1:
        return x
    return lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis)
