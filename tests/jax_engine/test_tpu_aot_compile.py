"""The main path's kernels compiled for a described TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached (on-chip-measurement guide, section 2). It refuses
what the chip would refuse: 64-bit min/max all-reduces, bitcasts of float64,
Pallas blocks that do not tile. Shapes are the real ones of
``chip_smoke.py``: 100M fact rows over 100,000 keys. Nothing runs; these
tests say nothing about results or times.

The compiles run in one child process per test worker, started by a
module-scoped fixture. A process that has loaded libtpu cannot fork safely
(the engine's UDF pool forks, and its children crash in libtpu's signal
handler), so the test workers themselves never load it. Nothing here
describes the topology at import, so every worker collects the same tests.
Run as a script, this file is that child: it reads case names on stdin and
answers one JSON line each.
"""

import json
import os
import subprocess
import sys
from typing import Any, Callable, Dict

import pytest

ROWS = 100_000_000
KEYS = 100_000
EXCHANGE_ROWS = 1 << 20  # compile time of the sort-based exchanges does not depend on it
_SUM_COUNT = (("s", "sum", 0, False), ("n", "count", 0, False))
_ROW_DTYPES = ("int64", "float64", "float32")

# ---------------------------------------------------------------- the child

_CASES: Dict[str, Callable[..., None]] = {}


def _case(fn: Callable[..., None]) -> Callable[..., None]:
    _CASES[fn.__name__] = fn
    return fn


def _arg(mesh: Any, n: Any, dtype: Any, spec: Any = None) -> Any:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fugue_tpu.parallel.mesh import ROW_AXIS

    shape = (n,) if isinstance(n, int) else n
    spec = P(ROW_AXIS) if spec is None else spec
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _mesh(topo: Any, chips: int) -> Any:
    from fugue_tpu.parallel.mesh import build_mesh

    return build_mesh(devices=list(topo.devices)[:chips])


@_case
def dense_segment_aggregate(topo: Any) -> None:
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from fugue_tpu.ops import segment

    mesh = _mesh(topo, 1)
    f = segment._get_compiled_dense(mesh, segment.dense_buckets(KEYS), _SUM_COUNT)
    args = (
        _arg(mesh, ROWS, jnp.int64),
        _arg(mesh, (), jnp.int64, P()),
        _arg(mesh, ROWS, jnp.float64),
        _arg(mesh, ROWS, jnp.bool_),
    )
    mem = f.lower(*args).compile().memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 30, mem


@_case
def sort_segment_aggregate(topo: Any) -> None:
    import jax.numpy as jnp

    from fugue_tpu.ops import segment

    mesh = _mesh(topo, 1)
    f = segment._get_compiled_kernel(mesh, 1, _SUM_COUNT)
    f.lower(
        _arg(mesh, ROWS, jnp.int64), _arg(mesh, ROWS, jnp.float64), _arg(mesh, ROWS, jnp.bool_)
    ).compile()


@_case
def join_probe(topo: Any) -> None:
    """Broadcast probe of the fact table against the 100,000-row dimension
    table: one key, two value columns (int64 region, float64 weight)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from fugue_tpu.ops import join

    mesh = _mesh(topo, 1)
    f = join._get_compiled_probe(mesh, "inner", 1, 2, ("int64", "int64", "float64"), False)
    rep = P()
    f.lower(
        _arg(mesh, ROWS, jnp.bool_),
        _arg(mesh, KEYS, jnp.uint64, rep),
        _arg(mesh, KEYS, jnp.int32, rep),
        _arg(mesh, 1, jnp.int64, rep),
        _arg(mesh, ROWS, jnp.int64),
        _arg(mesh, KEYS, jnp.int64, rep),
        _arg(mesh, KEYS, jnp.int64, rep),
        _arg(mesh, KEYS, jnp.float64, rep),
    ).compile()


def _rows(mesh: Any, n: int) -> tuple:
    import jax.numpy as jnp

    return tuple(_arg(mesh, n, jnp.dtype(d)) for d in _ROW_DTYPES)


@_case
def exchange_rows(topo: Any) -> None:
    """The copartition rung: one all_to_all over the 4-chip mesh."""
    import jax.numpy as jnp

    from fugue_tpu.ops import shuffle

    mesh, n = _mesh(topo, 4), EXCHANGE_ROWS
    dest = (_arg(mesh, n, jnp.int32), _arg(mesh, n, jnp.bool_))
    shuffle._get_compiled_counts(mesh).lower(*dest).compile()
    f = shuffle._get_compiled_exchange(mesh, _ROW_DTYPES, n // 4)
    assert "all-to-all" in f.lower(*dest, *_rows(mesh, n)).compile().as_text()


@_case
def staged_exchange_rows(topo: Any) -> None:
    """The device-exchange rung: the fused staged ppermute schedule."""
    import jax.numpy as jnp

    from fugue_tpu.shuffle import exchange

    mesh, n = _mesh(topo, 4), EXCHANGE_ROWS
    cap, out_cap = n // 8, n // 2
    f = exchange._get_compiled_schedule(mesh, _ROW_DTYPES, cap, out_cap, 1)
    args = (
        _arg(mesh, n, jnp.int32),
        _arg(mesh, n, jnp.bool_),
        _arg(mesh, 4, jnp.int32),
        *_rows(mesh, n),
        *_rows(mesh, 4 * out_cap),
    )
    assert "collective-permute" in f.lower(*args).compile().as_text()


@_case
def min_max(topo: Any, chips: str, op: str, dtype: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from fugue_tpu.ops import collectives
    from fugue_tpu.parallel.mesh import ROW_AXIS

    mesh = _mesh(topo, int(chips))
    reduce = getattr(collectives, op)

    def kernel(x: Any) -> Any:
        return reduce(x.min(), ROW_AXIS)[None]

    f = jax.jit(shard_map(kernel, mesh=mesh, in_specs=P(ROW_AXIS), out_specs=P()))
    arg = _arg(mesh, 4096, jnp.dtype(dtype))
    assert arg.dtype == jnp.dtype(dtype), "x64 is off: 64-bit cases would test 32-bit types"
    f.lower(arg).compile()


@_case
def all_to_all(topo: Any) -> None:
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from fugue_tpu.ops import collectives
    from fugue_tpu.parallel.mesh import ROW_AXIS

    mesh = _mesh(topo, 4)

    def kernel(x: Any) -> Any:
        return collectives.all_to_all(x.reshape(4, -1), ROW_AXIS, 0, 0).reshape(-1)

    f = jax.jit(shard_map(kernel, mesh=mesh, in_specs=P(ROW_AXIS), out_specs=P(ROW_AXIS)))
    assert "all-to-all" in f.lower(_arg(mesh, 4096, jnp.int64)).compile().as_text()


@_case
def pallas_bin_sum_count(topo: Any, buckets: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from fugue_tpu.ops import pallas_groupby

    mesh, n, rep = _mesh(topo, 1), 1 << 20, P()

    def kernel(k: Any, v: Any, m: Any) -> Any:
        return pallas_groupby.bin_sum_count_pallas(k, v, m, int(buckets))

    args = (_arg(mesh, n, jnp.int32, rep), _arg(mesh, n, jnp.float32, rep), _arg(mesh, n, jnp.bool_, rep))
    if int(buckets) > pallas_groupby.MAX_BUCKETS:
        try:
            jax.jit(kernel).lower(*args)
        except ValueError:
            return
        raise AssertionError(f"the pallas kernel took {buckets} buckets")
    assert "tpu_custom_call" in jax.jit(kernel).lower(*args).compile().as_text()


def _serve() -> None:
    import jax

    import fugue_tpu.jax  # noqa: F401  the engine's own x64 setting

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        print(json.dumps({"unavailable": str(e)[:500]}), flush=True)
        return
    print(json.dumps({"ready": True}), flush=True)
    for line in sys.stdin:
        name, *params = line.strip().split(":")
        try:
            _CASES[name](topo, *params)
            print(json.dumps({"ok": True}), flush=True)
        except Exception as e:
            print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {str(e)[:2000]}"}), flush=True)


# ---------------------------------------------------------------- the tests


@pytest.fixture(scope="module")
def aot() -> Any:
    """Compile one named case in this worker's child; fail with what the
    compiler said, skip when no v5e:2x2 topology can be described here."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    env.setdefault("TPU_LOG_DIR", "disabled")
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        env=env,
    )
    hello = json.loads(child.stdout.readline() or '{"unavailable": "child died"}')

    def compile_case(*name: Any) -> None:
        if "unavailable" in hello:
            pytest.skip(f"no v5e:2x2 topology can be described here: {hello['unavailable']}")
        child.stdin.write(":".join(str(p) for p in name) + "\n")
        child.stdin.flush()
        reply = json.loads(child.stdout.readline() or '{"ok": false, "error": "child died"}')
        assert reply["ok"], reply["error"]

    yield compile_case
    child.stdin.close()
    child.wait(timeout=60)


def test_dense_segment_aggregate(aot):
    aot("dense_segment_aggregate")


def test_sort_segment_aggregate(aot):
    aot("sort_segment_aggregate")


def test_join_probe(aot):
    aot("join_probe")


def test_exchange_rows(aot):
    aot("exchange_rows")


def test_staged_exchange_rows(aot):
    aot("staged_exchange_rows")


@pytest.mark.parametrize("dtype", ["int32", "float32", "int64", "float64", "uint64"])
@pytest.mark.parametrize("op", ["pmin", "pmax"])
@pytest.mark.parametrize("chips", [1, 4])
def test_min_max_collectives(aot, chips, op, dtype):
    """v5e lowers a 64-bit all-reduce only as a Sum; the wrappers must
    still compile for every key and value width."""
    aot("min_max", chips, op, dtype)


def test_all_to_all(aot):
    aot("all_to_all")


@pytest.mark.parametrize("buckets", [1024, 2048, 1 << 18])
def test_pallas_bin_sum_count(aot, buckets):
    """Compiles up to its widest table; ``_DENSE_MAX_RANGE`` buckets
    raise rather than run a grid that re-reads every row per tile."""
    aot("pallas_bin_sum_count", buckets)


if __name__ == "__main__":
    _serve()
