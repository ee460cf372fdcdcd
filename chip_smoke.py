#!/usr/bin/env python3
"""Drive the engine's main path once on the chip, at the size of one real
deployment, and check every result against a pandas/numpy oracle.

The deployment is ``BASELINE.json`` config #3, "cogroup/join + aggregate,
100M rows": a fact table of 100M rows (``k`` int64 uniform over 100,000
keys, ``v`` float64, ``w`` float32) and a dimension table of the 100,000
keys (``region`` int64 in 0..24, ``weight`` float64), made from ``--seed``.

One process, one ``JaxExecutionEngine``, the public entry points:

1. ``aggregate`` by ``k``: sum/count/avg of ``v`` (the dense device aggregate)
2. workflow: fact ⋈ dim on ``k``, then sum(``v``×``weight``) and count by
   ``region`` (config #3)
3. FugueSQL: ``LOAD`` parquet → ``SELECT … WHERE w > 0.5 GROUP BY k`` →
   ``TRANSFORM`` with a pandas UDF (config #2)
4. ``transform()`` with a compiled ``Dict[str, jax.Array]`` UDF over the
   whole fact table
5. ``transform()`` groupby-apply with a pandas UDF, 1M rows, 1,000 groups
   (config #1)
6. an in-process ``EngineServer`` on the same engine answers 4 submissions
   of phase 2's workflow factory from 2 tenants

Each phase prints one JSON line of bring-up diagnostics (rows, cold and warm
seconds, peak device bytes, the engine's path counters); they are not
benchmark metrics. The last line is ``{"ok": true, "device": {...}}``, printed
only after every phase matched. A run that finds no TPU exits non-zero
before any phase.

``--chips 4`` runs phase 2 only, on a 4-chip mesh, once (cold) on the
copartition rung and once on the device-exchange rung, each against its
oracle, and checks that every device frame is split into 4 shards of about a
quarter of the rows each.

``--rehearse`` allows the CPU backend for a rehearsal at a small size (give
``--rows`` and friends); it prints no ``ok`` line.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, Tuple

import numpy as np
import pandas as pd

REGIONS = 25
TENANTS = ("tenant-a", "tenant-b")


def _parse(argv: Any) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--rows", type=int, default=100_000_000)
    p.add_argument("--keys", type=int, default=100_000)
    p.add_argument("--udf-rows", type=int, default=1_000_000)
    p.add_argument("--udf-groups", type=int, default=1_000)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true")
    return p.parse_args(argv)


# ---------------------------------------------------------------- data


def make_tables(rows: int, keys: int, seed: int) -> Tuple[pd.DataFrame, pd.DataFrame]:
    rng = np.random.default_rng(seed)
    fact = pd.DataFrame(
        {
            "k": rng.integers(0, keys, rows, dtype=np.int64),
            "v": rng.random(rows),
            "w": rng.random(rows, dtype=np.float32),
        }
    )
    dim = pd.DataFrame(
        {
            "k": rng.permutation(keys).astype(np.int64),
            "region": rng.integers(0, REGIONS, keys, dtype=np.int64),
            "weight": rng.random(keys),
        }
    )
    return fact, dim


def make_udf_frame(rows: int, groups: int, seed: int) -> pd.DataFrame:
    rng = np.random.default_rng(seed + 1)
    return pd.DataFrame(
        {"k": rng.integers(0, groups, rows, dtype=np.int64), "v": rng.random(rows)}
    )


# ---------------------------------------------------------------- checks


def _close(got: np.ndarray, exp: np.ndarray, what: str) -> float:
    """Largest error relative to the column's largest value (a demeaned
    value near zero has no meaningful relative error of its own); raises
    past the float64 tolerance."""
    got = np.asarray(got, dtype=np.float64)
    exp = np.asarray(exp, dtype=np.float64)
    if got.shape != exp.shape:
        raise AssertionError(f"{what}: shape {got.shape} != oracle {exp.shape}")
    scale = max(float(np.max(np.abs(exp), initial=0.0)), 1e-300)
    err = float(np.max(np.abs(got - exp), initial=0.0)) / scale
    if not err <= 1e-9:
        raise AssertionError(f"{what}: error {err} of the largest value > 1e-9")
    return err


def _equal(got: Any, exp: Any, what: str) -> None:
    if not np.array_equal(np.asarray(got), np.asarray(exp)):
        raise AssertionError(f"{what}: differs from the oracle")


def check_key_aggregate(res: pd.DataFrame, k: np.ndarray, v: np.ndarray, keys: int) -> float:
    res = res.sort_values("k").reset_index(drop=True)
    n = np.bincount(k, minlength=keys)
    s = np.bincount(k, weights=v, minlength=keys)
    present = np.nonzero(n)[0]
    _equal(res["k"], present, "aggregate keys")
    _equal(res["n"], n[present], "aggregate count")
    return max(
        _close(res["s"], s[present], "aggregate sum"),
        _close(res["m"], s[present] / n[present], "aggregate avg"),
    )


def region_oracle(fact: pd.DataFrame, dim: pd.DataFrame, keys: int) -> pd.DataFrame:
    region = np.zeros(keys, dtype=np.int64)
    weight = np.zeros(keys)
    region[dim["k"].to_numpy()] = dim["region"].to_numpy()
    weight[dim["k"].to_numpy()] = dim["weight"].to_numpy()
    k = fact["k"].to_numpy()
    r = region[k]
    s = np.bincount(r, weights=fact["v"].to_numpy() * weight[k], minlength=REGIONS)
    n = np.bincount(r, minlength=REGIONS)
    present = np.nonzero(n)[0]
    return pd.DataFrame({"region": present, "s": s[present], "n": n[present]})


def check_region(res: pd.DataFrame, exp: pd.DataFrame, what: str) -> float:
    res = res.sort_values("region").reset_index(drop=True)
    _equal(res["region"], exp["region"], f"{what} regions")
    _equal(res["n"], exp["n"], f"{what} count")
    return _close(res["s"], exp["s"], f"{what} sum")


# ---------------------------------------------------------------- engine path


def region_workflow(fact: Any, dim: Any) -> Any:
    """Config #3 as a workflow: fact ⋈ dim on k, sum(v×weight) and count by
    region."""
    from fugue_tpu import FugueWorkflow
    from fugue_tpu.column import col, functions as ff

    dag = FugueWorkflow()
    (
        dag.df(fact)
        .inner_join(dag.df(dim), on=["k"])
        .select(col("region"), (col("v") * col("weight")).alias("vw"))
        .partition_by("region")
        .aggregate(ff.sum(col("vw")).alias("s"), ff.count(col("vw")).alias("n"))
        .yield_dataframe_as("r", as_local=True)
    )
    return dag


def run_region_workflow(eng: Any, fact: Any, dim: Any) -> pd.DataFrame:
    dag = region_workflow(fact, dim)
    dag.run(eng)
    return dag.yields["r"].result.as_pandas()


def mean_of(df: pd.DataFrame) -> pd.DataFrame:
    df["m"] = df["s"] / df["n"]
    return df


def demean(df: pd.DataFrame) -> pd.DataFrame:
    df["v"] = df["v"] - df["v"].mean()
    return df


class _setup:
    """Prints how long a piece of set-up or checking took."""

    def __init__(self, what: str):
        self.what = what

    def __enter__(self) -> None:
        self.t0 = time.perf_counter()

    def __exit__(self, *exc: Any) -> None:
        if exc[0] is None:
            print(json.dumps({"setup": self.what, "seconds": time.perf_counter() - self.t0}), flush=True)


def _block(res: Any) -> Any:
    """Wait for the device: a frame's timing ends when its columns exist."""
    import jax

    cols = getattr(res, "device_cols", None)
    if cols:
        jax.block_until_ready(list(cols.values()))
    return res


def _flat(d: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[f"{prefix}{k}"] = v
    return out


_COUNTERS = (
    "plan.segments_lowered",
    "plan.segments_executed",
    "plan.segments_fallback",
    "shuffle.device_exchange_joins",
    "shuffle.device_exchange_fallbacks",
    "shuffle.spill_joins",
)


# the persistent compile cache's own events, counted from the start
CACHE_EVENTS = {"compile_requests_use_cache": 0, "cache_hits": 0, "cache_misses": 0}


def _on_jax_event(name: str, **_: Any) -> None:
    short = name.rsplit("/", 1)[-1]
    if short in CACHE_EVENTS:
        CACHE_EVENTS[short] += 1


class Phase:
    """Times a phase cold (compilation included) and warm, and records the
    engine's path counters and the compile cache's hits over both runs."""

    def __init__(self, eng: Any, name: str, rows: int, join: bool = False):
        self.eng = eng
        self.join = join
        self.line: Dict[str, Any] = {"phase": name, "rows": rows}

    def run(self, fn: Callable[[], Any], cold: bool = True, warm: bool = True) -> Any:
        """``cold=False``: the phase's programs were compiled by an earlier
        phase, so its one run is a warm run. ``warm=False``: one cold run
        only."""
        before = _flat(self.eng.stats())
        cache_before = dict(CACHE_EVENTS)
        t0 = time.perf_counter()
        out = _block(fn())
        self.line["cold_s"] = time.perf_counter() - t0 if cold else None
        self.line["warm_s"] = None
        if cold and warm:
            t0 = time.perf_counter()
            _block(fn())
        if warm:
            self.line["warm_s"] = time.perf_counter() - t0
        after = _flat(self.eng.stats())
        delta = {k: after[k] - before.get(k, 0) for k in after}
        self.line["counters"] = {k: delta.get(k, 0) for k in _COUNTERS}
        self.line["kernels"] = {
            k[len("jit_cache.by_label.") :]: v
            for k, v in delta.items()
            if k.startswith("jit_cache.by_label.") and v
        }
        if self.join:
            self.line["join_strategy"] = self.eng.last_join_strategy
        self.line["compile_cache"] = {k: CACHE_EVENTS[k] - cache_before[k] for k in CACHE_EVENTS}
        return out

    def require_device_path(self) -> None:
        c = self.line["counters"]
        if c["plan.segments_fallback"] != 0:
            raise AssertionError(f"{self.line['phase']}: a lowered segment fell back")
        if c["shuffle.device_exchange_fallbacks"] != 0:
            raise AssertionError(f"{self.line['phase']}: device exchange fell back")
        if not self.line["kernels"]:
            raise AssertionError(f"{self.line['phase']}: no device kernel ran")
        if self.join and self.line["join_strategy"] in (None, "host"):
            raise AssertionError(f"{self.line['phase']}: join ran on the host")

    def emit(self, **extra: Any) -> None:
        import jax

        self.line.update(extra)
        stats = jax.devices()[0].memory_stats() or {}
        self.line["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        self.line["live_bytes"] = sum(a.nbytes for a in jax.live_arrays())
        print(json.dumps(self.line), flush=True)


# ---------------------------------------------------------------- phases


def one_chip(args: argparse.Namespace, eng: Any, fact_pd: pd.DataFrame, dim_pd: pd.DataFrame) -> None:
    from typing import Dict as TDict

    import jax

    import fugue_tpu.api as fa
    from fugue_tpu.column import col, functions as ff
    from fugue_tpu.serve import EngineServer
    from fugue_tpu.sql import fugue_sql

    with _setup("ingest"):
        fact = eng.persist(eng.to_df(fact_pd))
        dim = eng.persist(eng.to_df(dim_pd))
    k = fact_pd["k"].to_numpy()
    v = fact_pd["v"].to_numpy()

    # 1. dense device aggregate
    ph = Phase(eng, "aggregate", args.rows)
    res = ph.run(
        lambda: fa.aggregate(
            fact,
            partition_by="k",
            engine=eng,
            as_fugue=True,
            s=ff.sum(col("v")),
            n=ff.count(col("v")),
            m=ff.avg(col("v")),
        )
    )
    with _setup("check aggregate"):
        err = check_key_aggregate(res.as_pandas(), k, v, args.keys)
    ph.require_device_path()
    ph.emit(rel_err=err)

    # 2. config #3 through the workflow API
    with _setup("region oracle"):
        direct = region_oracle(fact_pd, dim_pd, args.keys)
    ph = Phase(eng, "join_aggregate", args.rows, join=True)
    res = ph.run(lambda: run_region_workflow(eng, fact, dim))
    err = check_region(res, direct, "join_aggregate")
    ph.require_device_path()
    ph.emit(rel_err=err)

    # 3. config #2 through FugueSQL over parquet
    tmp = tempfile.mkdtemp(prefix="fugue_chip_smoke_")
    try:
        path = os.path.join(tmp, "fact.parquet")
        with _setup("write parquet"):
            fact_pd.to_parquet(path, index=False)
        sql = f"""
        src = LOAD "{path}"
        agg = SELECT k, SUM(v) AS s, COUNT(*) AS n FROM src WHERE w > 0.5 GROUP BY k
        TRANSFORM agg USING mean_of SCHEMA k:long,s:double,n:long,m:double
        """
        ph = Phase(eng, "sql_parquet", args.rows)
        res = ph.run(lambda: fugue_sql(sql, mean_of=mean_of, engine=eng, as_fugue=True))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    with _setup("check sql"):
        keep = fact_pd["w"].to_numpy() > 0.5
        err = check_key_aggregate(res.as_pandas(), k[keep], v[keep], args.keys)
    ph.require_device_path()
    ph.emit(rel_err=err)

    # 4. compiled Dict[str, jax.Array] UDF over the whole fact table
    def scale(cols: TDict[str, jax.Array]) -> TDict[str, jax.Array]:
        return {"k": cols["k"], "z": cols["v"] * 2.0 + cols["w"]}

    ph = Phase(eng, "compiled_transform", args.rows)
    res = ph.run(
        lambda: fa.transform(fact, scale, schema="k:long,z:double", engine=eng, as_fugue=True)
    )
    with _setup("check compiled transform"):
        got = res.as_pandas()
        _equal(got["k"], k, "compiled transform keys")
        z = v * 2.0 + fact_pd["w"].to_numpy().astype(np.float64)
        err = _close(got["z"], z, "compiled transform")
    ph.require_device_path()
    ph.emit(rel_err=err)
    del got, res

    # 5. config #1: groupby-apply with a pandas UDF
    udf_pd = make_udf_frame(args.udf_rows, args.udf_groups, args.seed)
    udf_df = eng.persist(eng.to_df(udf_pd))
    ph = Phase(eng, "pandas_groupby_apply", args.udf_rows)
    res = ph.run(
        lambda: fa.transform(
            udf_df, demean, schema="*", partition={"by": ["k"]}, engine=eng, as_fugue=True
        )
    )
    got = res.as_pandas().sort_values(["k", "v"]).reset_index(drop=True)
    exp = udf_pd.copy()
    exp["v"] = exp["v"] - exp.groupby("k")["v"].transform("mean")
    exp = exp.sort_values(["k", "v"]).reset_index(drop=True)
    _equal(got["k"], exp["k"], "groupby-apply keys")
    err = _close(got["v"], exp["v"], "groupby-apply")
    ph.emit(rel_err=err)

    # 6. the served path: 2 tenants, 4 submissions of phase 2's factory
    ph = Phase(eng, "served_join_aggregate", args.rows, join=True)

    def serve() -> list:
        with EngineServer(eng) as srv:
            subs = [
                srv.submit(lambda: region_workflow(fact, dim), tenant=TENANTS[i % 2])
                for i in range(4)
            ]
            return [s.result(timeout=600).yields["r"].result.as_pandas() for s in subs]

    results = ph.run(serve, cold=False)
    err = max(check_region(r, direct, f"served result {i}") for i, r in enumerate(results))
    ph.emit(rel_err=err, submissions=len(results), tenants=len(TENANTS))


def four_chips(args: argparse.Namespace, mesh: Any, fact_pd: pd.DataFrame, dim_pd: pd.DataFrame) -> None:
    from fugue_tpu.constants import (
        FUGUE_TPU_CONF_JOIN_BROADCAST_MAX_ROWS,
        FUGUE_TPU_CONF_SHUFFLE_DEVICE_BUDGET,
    )

    direct = region_oracle(fact_pd, dim_pd, args.keys)
    fact_bytes = int(fact_pd.memory_usage(index=False).sum())
    rungs = {
        # the dimension table is past the broadcast threshold: both sides
        # co-partition by key hash with the all_to_all exchange
        "copartition": {FUGUE_TPU_CONF_JOIN_BROADCAST_MAX_ROWS: 0},
        # both sides past one device's budget but within four: the staged
        # ppermute exchange
        "device_exchange": {
            FUGUE_TPU_CONF_JOIN_BROADCAST_MAX_ROWS: 0,
            FUGUE_TPU_CONF_SHUFFLE_DEVICE_BUDGET: fact_bytes // 2,
        },
    }
    for rung, conf in rungs.items():
        eng = _engine(mesh, conf)
        fact = eng.persist(eng.to_df(fact_pd))
        dim = eng.persist(eng.to_df(dim_pd))
        shards = {"fact": _quarters(fact, "fact"), "dim": _quarters(dim, "dim")}
        ph = Phase(eng, f"join_aggregate_{rung}", args.rows, join=True)
        # one cold run a rung: four chips cost four times as much a second
        res = ph.run(lambda: run_region_workflow(eng, fact, dim), warm=False)
        err = check_region(res, direct, rung)
        ph.require_device_path()
        if ph.line["join_strategy"] != rung:
            raise AssertionError(f"{rung}: join took {ph.line['join_strategy']}")
        joined = eng.join(fact, dim, how="inner", on=["k"])
        shards["joined"] = _quarters(joined, "joined", valid_only=True)
        ph.emit(rel_err=err, shard_rows=shards)
        eng.stop_engine()


def _quarters(df: Any, what: str, valid_only: bool = False) -> list:
    """Rows on each of the 4 devices; raises unless each holds about a
    quarter. Code that never ran on several chips may put all on one."""
    cols = df.device_cols
    arr = df.device_valid_mask() if valid_only else next(iter(cols.values()))
    shards = arr.addressable_shards
    if len(shards) != 4 or len({s.device for s in shards}) != 4:
        raise AssertionError(f"{what}: {len(shards)} addressable shards, want 4")
    rows = [int(np.asarray(s.data).sum()) if valid_only else int(s.data.shape[0]) for s in shards]
    total = sum(rows)
    if any(abs(r - total / 4) > 0.1 * total / 4 for r in rows):
        raise AssertionError(f"{what}: rows per shard {rows} are not about a quarter each")
    return rows


def _engine(mesh: Any, conf: Dict[str, Any]) -> Any:
    from fugue_tpu.constants import FUGUE_TPU_CONF_CACHE_ENABLED, FUGUE_TPU_CONF_TUNING_ENABLED
    from fugue_tpu.jax import JaxExecutionEngine

    # warm runs must execute, not replay the result cache; the tuner would
    # write what it learns into the source tree
    base = {FUGUE_TPU_CONF_CACHE_ENABLED: False, FUGUE_TPU_CONF_TUNING_ENABLED: False}
    return JaxExecutionEngine({**base, **conf}, mesh=mesh)


def main(argv: Any = None) -> int:
    args = _parse(argv)
    from fugue_tpu._utils.compile_cache import use_compile_cache

    env_set = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    cache_dir = use_compile_cache()
    import jax
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_jax_event)
    print(json.dumps({"compile_cache": cache_dir, "from_env": env_set}), flush=True)

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"want {args.chips} devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    if args.rows != 100_000_000 or args.keys != 100_000:
        print(
            json.dumps({"size_cut": {"rows": args.rows, "keys": args.keys}, "reason": "command line"}),
            flush=True,
        )

    from fugue_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(devices=devices)
    t0 = time.perf_counter()
    fact_pd, dim_pd = make_tables(args.rows, args.keys, args.seed)
    print(json.dumps({"setup": "tables", "seconds": time.perf_counter() - t0}), flush=True)
    if args.chips == 4:
        four_chips(args, mesh, fact_pd, dim_pd)
    else:
        eng = _engine(mesh, {})
        one_chip(args, eng, fact_pd, dim_pd)
        eng.stop_engine()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(json.dumps({"compile_cache": cache_dir, "entries": entries, **CACHE_EVENTS}), flush=True)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": dev}))
    else:
        print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
