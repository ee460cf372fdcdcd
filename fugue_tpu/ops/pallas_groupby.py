"""Binned (dense groupby) reductions via one-hot MXU matmuls — the
TPU-native alternative to XLA scatter-add.

Why: scatter on TPU serializes through the VPU's scalar update path,
while a histogram expressed as ``one_hot(keys) @ values`` rides the MXU
systolic array (the reference's analog of this choice is delegating
grouping to DuckDB's vectorized C++ engine,
``/root/reference/fugue_duckdb/execution_engine.py:137``; here the
hardware-matched primitive IS the design). Two implementations with one
contract:

- :func:`bin_sum_count_xla` — chunked ``lax.scan`` over rows, one-hot
  compare + matmul per chunk; pure jnp, runs on every backend, and XLA
  fuses the compare into the matmul operand feed.
- :func:`bin_sum_count_pallas` — a Pallas TPU kernel: a grid over row
  chunks, one-hot partial products accumulated into the VMEM-resident
  bucket table across the sequential steps (no HBM one-hot is ever
  materialized). It covers at most ``MAX_BUCKETS`` buckets, so the
  step's one-hot block stays within 1 MiB, and raises above that: a wider
  table would re-read every row once per bucket tile, and nothing has
  measured such a grid to win over scatter. ``interpret=True`` makes it
  testable on CPU.

Both compute per-bucket SUM and COUNT of float32 values in one pass.
float32 only: the MXU has no 64-bit path — f64 aggregation keeps the
scatter/XLA-emulation route (see ``ops/segment.py``), a deliberate
precision/speed split the engine picks per column dtype.

Exactness bound: the COUNT table also accumulates in float32 through the
matmul, so counts are exact only up to 2**24 rows per bucket — above
that, float32 cannot represent every integer and increments are lost.
The engine's dense-groupby path is NOT exposed to this: it keeps COUNT
in an int64 scatter (``segment.py``) and only routes the f32 SUM through
these kernels. Direct callers needing bigger per-bucket counts should
split their input or use the engine path.
"""

from typing import Any, Tuple

import jax

ROWS, LANES = 8, 128  # one grid step's rows, as one f32 (8, 128) tile
CHUNK = ROWS * LANES  # rows per grid step
# widest bucket table of the Pallas kernel: its one-hot block is
# (MAX_BUCKETS, LANES) f32 = 1 MiB of VMEM
MAX_BUCKETS = 2048


def _pad_inputs(keys: Any, values: Any, valid: Any, buckets: int):
    import jax.numpy as jnp

    n = keys.shape[0]
    padded = ((n + CHUNK - 1) // CHUNK) * CHUNK
    pad = padded - n
    if pad > 0:
        keys = jnp.pad(keys, (0, pad))
        values = jnp.pad(values, (0, pad))
        valid = jnp.pad(valid, (0, pad))  # False
    # invalid rows contribute 0 via the mask; clamp keys so the one-hot
    # compare never sees out-of-range ids
    keys = jnp.clip(keys, 0, buckets - 1).astype(jnp.int32)
    return keys, values, valid, padded // CHUNK


def bin_sum_count_xla(
    keys: Any, values: Any, valid: Any, buckets: int
) -> Tuple[Any, Any]:
    """Per-bucket (sum, count) of ``values`` grouped by ``keys`` via
    chunked one-hot matmuls. ``buckets`` must be a multiple of 128 on
    real TPUs for MXU alignment (any value works functionally)."""
    import jax
    import jax.numpy as jnp

    keys, values, valid, n_chunks = _pad_inputs(keys, values, valid, buckets)
    kc = keys.reshape(n_chunks, CHUNK)
    vc = values.astype(jnp.float32).reshape(n_chunks, CHUNK)
    mc = valid.astype(jnp.float32).reshape(n_chunks, CHUNK)
    iota = jnp.arange(buckets, dtype=jnp.int32)

    # vmap-over-chunks (not a scan): a scan carry would need replicated→
    # varying casts under shard_map, and XLA fuses the chunk matmuls +
    # final reduction into the same loop anyway
    def chunk(k: Any, v: Any, m: Any) -> Tuple[Any, Any]:
        onehot = (k[:, None] == iota[None, :]).astype(jnp.float32) * m[:, None]
        s = jnp.dot(v[None, :], onehot, preferred_element_type=jnp.float32)[0]
        c = jnp.dot(m[None, :], onehot, preferred_element_type=jnp.float32)[0]
        return s, c

    ps, pc = jax.vmap(chunk)(kc, vc, mc)
    return ps.sum(axis=0), pc.sum(axis=0).astype(jnp.int32)


def _bin_kernel(keys_ref, vals_ref, mask_ref, *out_refs):
    """One grid step: CHUNK rows' one-hot partial products accumulated
    into the ``(1, lanes)`` output blocks, which stay in VMEM across the
    sequential row axis. The first output sums the values, the second (if
    any) counts the valid rows."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for o in out_refs:
            o[:, :] = jnp.zeros_like(o)

    lanes = out_refs[0].shape[1]
    # bucket id of each one-hot row (2D iota: 1D iota does not lower on TPU)
    ids = jax.lax.broadcasted_iota(jnp.int32, (lanes, LANES), 0)
    for r in range(ROWS):
        k = keys_ref[pl.ds(r, 1), :]  # (1, LANES) int32
        m = mask_ref[pl.ds(r, 1), :]  # (1, LANES) f32
        onehot_t = (ids == k).astype(jnp.float32) * m  # (lanes, LANES)
        for o, w in zip(out_refs, (vals_ref[pl.ds(r, 1), :], m)):
            # (1, LANES) x (lanes, LANES)^T -> (1, lanes) on the MXU
            o[:, :] += jax.lax.dot_general(
                w, onehot_t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )


def bin_sum_idx(idx: Any, values: Any, buckets: int, backend: str) -> Any:
    """Per-bucket SUM of pre-masked float32 ``values`` routed by bucket id
    ``idx`` (invalid rows carry 0 and any in-range id) — the drop-in
    alternative to ``zeros(buckets).at[idx].add(values)`` used by the
    dense groupby kernel (``segment.py``). ``backend``: "onehot" (chunked
    jnp) or "pallas" (the sum-only TPU kernel — pallas outputs can't be
    dead-code-eliminated, so the count table is not computed here)."""
    import jax.numpy as jnp

    ones = jnp.ones(idx.shape[0], dtype=jnp.float32)
    if backend == "pallas":
        return bin_sum_pallas(idx, values, ones, buckets)
    sums, _ = bin_sum_count_xla(idx, values, ones, buckets)
    return sums


def _pallas_binned(n_out: int, keys, values, valid, buckets, interpret):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if buckets > MAX_BUCKETS:
        raise ValueError(
            f"the pallas dense sum covers at most {MAX_BUCKETS} buckets, "
            f"not {buckets}; use the scatter backend"
        )
    # the accumulator's last dim must tile to the TPU's 128-lane registers;
    # round the bucket table up and slice the result back
    lanes = ((buckets + LANES - 1) // LANES) * LANES
    keys, values, valid, n_chunks = _pad_inputs(keys, values, valid, buckets)
    shape = (n_chunks * ROWS, LANES)
    kc = keys.reshape(shape)
    vc = values.astype(jnp.float32).reshape(shape)
    mc = valid.astype(jnp.float32).reshape(shape)

    # block indices stay int32 (a literal 0 would be int64 under x64,
    # which Mosaic refuses)
    row_spec = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0 * i))
    acc_spec = pl.BlockSpec((1, lanes), lambda i: (0 * i, 0 * i))
    out = pl.pallas_call(
        _bin_kernel,
        grid=(n_chunks,),
        in_specs=[row_spec, row_spec, row_spec],
        out_specs=[acc_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((1, lanes), jnp.float32)] * n_out,
        interpret=interpret,
    )(kc, vc, mc)
    return [o[:, :buckets] for o in out]


def bin_sum_pallas(
    keys: Any, values: Any, valid: Any, buckets: int, interpret: bool = False
) -> Any:
    """Per-bucket SUM only (the dense-kernel hot path)."""
    (sums,) = _pallas_binned(1, keys, values, valid, buckets, interpret)
    return sums[0]


def bin_sum_count_pallas(
    keys: Any, values: Any, valid: Any, buckets: int, interpret: bool = False
) -> Tuple[Any, Any]:
    """Pallas TPU version of :func:`bin_sum_count_xla` — identical
    contract; ``interpret=True`` runs the kernel in the Pallas
    interpreter (CPU-testable)."""
    import jax.numpy as jnp

    sums, cnts = _pallas_binned(2, keys, values, valid, buckets, interpret)
    return sums[0], cnts[0].astype(jnp.int32)
