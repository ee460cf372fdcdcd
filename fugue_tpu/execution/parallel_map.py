"""Process-pool execution of per-partition UDFs, with supervised recovery.

The reference runs transformers concurrently across cluster workers (Spark
``mapInPandas`` over executors, ``fugue_spark/execution_engine.py:237-330``;
Dask ``map_partitions``, ``fugue_dask/execution_engine.py:93-183``). The
TPU-native equivalent for the HOST side of the map path is a fork-based
process pool over logical partitions: pandas UDFs hold the GIL, so threads
don't help, while ``fork`` gives every worker copy-on-write access to the
parent's already-materialized pandas frame — no input serialization at all.
Only the (usually much smaller) per-partition outputs cross back, as arrow
tables.

Partitions are split into more chunks than workers (dynamic balancing for
skewed group sizes), each chunk a contiguous partition range so global
partition numbering is preserved.

Dispatch is SUPERVISED (``fugue_tpu/resilience``): chunks go out via
``apply_async`` with a per-chunk deadline, the driver watches the pool's
worker processes, and recovery follows the graceful-degradation order
**parallel → retry → serial → raise**:

1. a dead worker (OOM-kill, segfault, injected SIGKILL) or an expired
   chunk deadline tears down the wave; finished chunk results are kept;
2. lost/failed chunks retry on a FRESH fork pool under the engine's
   ``fugue.tpu.retry.*`` policy;
3. chunks that exhaust retries (or fail deterministically — "poison"
   partitions) are quarantined to serial in-driver execution, which also
   yields clean tracebacks;
4. only if the serial path fails too does the map raise, with a
   per-partition failure report (``ParallelMapError``).

Every recovery step increments the engine's ``resilience_stats``.

Not engaged when:
- the platform has no ``fork`` (non-Linux/macOS spawn semantics), or the
  process has loaded the TPU runtime, whose threads do not survive a fork,
- the transformer carries a worker→driver RPC callback (the in-process
  ``NativeRPCServer`` can't cross a process boundary; such transformers run
  serially, matching the reference's local engine),
- the frame is below ``fugue.tpu.map.parallel_min_rows`` (pool setup costs
  ~100ms — tiny frames are faster serial),
- everything fits one chunk (``len(chunks) <= 1``): a pool of one worker
  has no concurrency to offer, so the chunk runs serially in-driver.
"""

import multiprocessing as mp
import os
import threading
import time
import warnings
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

from ..resilience import (
    NULL_INJECTOR,
    SITE_MAP_CHUNK,
    SITE_MAP_DISPATCH,
    ChunkTimeoutError,
    Deadline,
    FailureCategory,
    FaultInjector,
    ParallelMapError,
    ResilienceStats,
    RetryPolicy,
    WorkerLostError,
    classify_failure,
)

# set in the parent immediately before forking; children inherit the memory
# image, so the frame and the (arbitrary, unpicklable) UDF need no transport.
# the lock spans set-state → fork → drain: concurrent map calls (workflow
# concurrency > 1) must not clobber each other's state mid-fork
_FORK_STATE: dict = {}
_FORK_LOCK = threading.Lock()

# polling cadence of the supervision loop; cheap (ready()/exitcode checks)
_POLL_INTERVAL = 0.01


def fork_available() -> bool:
    """The one rule for forking in this repo (the UDF pool here, the bench
    chaos smokes' replicas): only where ``fork`` exists and this process
    has not loaded the TPU runtime."""
    try:
        return "fork" in mp.get_all_start_methods() and not _holds_libtpu()
    except Exception:
        return False


_LIBTPU_LOADED = False


def _holds_libtpu() -> bool:
    """True once this process has loaded the TPU runtime (a chip backend,
    or a described topology). Its threads and signal handlers do not
    survive ``fork``: forked pool workers crash, so the pool runs serial.
    The runtime is never unloaded, so a True answer is kept."""
    global _LIBTPU_LOADED
    if not _LIBTPU_LOADED:
        try:
            with open("/proc/self/maps") as f:
                _LIBTPU_LOADED = any("libtpu" in line for line in f)
        except OSError:
            pass
    return _LIBTPU_LOADED


def map_func_parallel_safe(map_func: Callable) -> bool:
    """True when the UDF can run in a forked worker.

    A transformer holding an in-process RPC callback must stay in the
    driver process: a forked child would invoke its own copy of the handler
    and the driver would never see the calls.
    """
    runner = getattr(map_func, "__self__", None)
    tf = getattr(runner, "transformer", None)
    if tf is None:
        return True
    return getattr(tf, "_callback", None) is None


def split_chunks(sizes: Sequence[int], n_chunks: int) -> List[Any]:
    """Split partition ids [0..len) into ≤n_chunks contiguous runs balanced
    by total row count (greedy quantile cuts over the cumulative sizes)."""
    n = len(sizes)
    if n == 0:
        return []
    n_chunks = max(1, min(n_chunks, n))
    cum = np.cumsum(np.asarray(sizes, dtype=np.int64))
    total = int(cum[-1])
    bounds = [0]
    for q in range(1, n_chunks):
        target = total * q // n_chunks
        pos = int(np.searchsorted(cum, target, side="left")) + 1
        if pos > bounds[-1] and pos < n:
            bounds.append(pos)
    bounds.append(n)
    return [range(a, b) for a, b in zip(bounds[:-1], bounds[1:]) if b > a]


def _exec_partition(
    no: int,
    pdf: pd.DataFrame,
    groups: List[Any],
    map_func: Callable,
    cursor: Any,
    schema: Any,
    output_schema: Any,
    wrap: Callable,
    to_tbl: Callable,
) -> pa.Table:
    """Run the UDF over one logical partition — shared by the forked worker
    body and the driver's serial/quarantine paths."""
    idx = groups[no]
    if isinstance(idx, slice):
        sub = pdf.iloc[idx].reset_index(drop=True)
    else:
        sub = pdf.take(idx).reset_index(drop=True)
    part = wrap(sub, schema)
    cursor.set(lambda p=part: p.peek_array(), no, 0)
    res = map_func(cursor, part)
    return to_tbl(res, output_schema)


def _run_chunk(part_ids: Any) -> Dict[str, Any]:
    """Worker body: run the inherited UDF over a contiguous partition range.

    Results serialize as arrow IPC streams — pyarrow tables cross process
    boundaries far cheaper than pickled pandas frames. The return payload
    also carries the worker's OBSERVABILITY delta across the fork
    boundary: per-chunk resilience counters and any trace spans recorded
    while the chunk ran (a forked child's in-memory increments are
    otherwise invisible to the driver). Failed/killed chunks can't ship a
    delta — by design the payload rides the success path only.
    """
    from ..obs import get_span_metrics, get_tracer

    st = _FORK_STATE
    injector: FaultInjector = st.get("injector", NULL_INJECTOR)
    tracer = get_tracer()
    mark = tracer.mark()
    # histogram counterpart of the span mark: snapshot the (fork-inherited,
    # copy-on-write) span-metric state so only THIS chunk's observations
    # ship home as a mergeable delta
    hist_mark = get_span_metrics().snapshot() if tracer.enabled else None
    counters: Dict[str, int] = {"map.worker_chunks": 1}
    rows_out = 0
    out: List[bytes] = []
    with tracer.span(
        "map.worker_chunk",
        cat="worker",
        parent=st.get("trace_parent"),
        worker_pid=os.getpid(),
        partitions=len(part_ids),
    ) as chunk_sp:
        # fault-injection site: a `kill` here SIGKILLs this worker
        # mid-chunk, exactly the OOM-killer scenario the supervisor must
        # recover from
        injector.fire(SITE_MAP_CHUNK)
        for no in part_ids:
            with tracer.span("map.partition", cat="worker", partition=no) as sp:
                tbl = _exec_partition(
                    no,
                    st["pdf"],
                    st["groups"],
                    st["map_func"],
                    st["cursor"],
                    st["schema"],
                    st["output_schema"],
                    st["wrap_df"],
                    st["to_arrow"],
                )
                sp.set(rows_out=tbl.num_rows)
            counters["map.worker_partitions"] = (
                counters.get("map.worker_partitions", 0) + 1
            )
            rows_out += tbl.num_rows
            sink = pa.BufferOutputStream()
            with pa.ipc.new_stream(sink, tbl.schema) as w:
                w.write_table(tbl)
            out.append(sink.getvalue().to_pybytes())
        chunk_sp.set(rows_out=rows_out)
    counters["map.worker_rows_out"] = rows_out
    payload: Dict[str, Any] = {
        "blobs": out,
        "counters": counters,
        "spans": tracer.take_since(mark),
    }
    if hist_mark is not None:
        payload["hist"] = get_span_metrics().delta_since(hist_mark)
    return payload


def _harvest_chunk(payload: Any, stats: ResilienceStats) -> List[pa.Table]:
    """Driver side of the fork-boundary protocol: merge the worker's
    counter delta into the driver registry, ingest its spans into the
    global tracer, merge its histogram delta into the span-metrics store
    (label-keyed, never pid-keyed — associative across any worker order),
    and decode the arrow blobs."""
    if isinstance(payload, dict):
        stats.merge(payload.get("counters", {}))
        spans = payload.get("spans")
        if spans:
            from ..obs import get_tracer

            get_tracer().ingest(spans)
        hist = payload.get("hist")
        if hist:
            from ..obs import get_span_metrics

            get_span_metrics().merge(hist)
        blobs = payload["blobs"]
    else:  # defensive: pre-ISSUE-3 plain-list payload
        blobs = payload
    return [_decode_blob(b) for b in blobs]


def _decode_blob(blob: bytes) -> pa.Table:
    with pa.ipc.open_stream(pa.BufferReader(blob)) as r:
        return r.read_all()


@contextmanager
def _quiet_fork_warnings():
    """children never touch JAX (host-only pandas UDFs by the format-hint
    gate). On the CPU backend the fork-vs-threads warning is noise; on an
    accelerator backend (libtpu holds runtime threads) keep the warning
    visible — forking there is riskier and worth the operator's attention.
    The filter spans the whole supervised phase because ``Pool`` forks
    again mid-wave when it respawns a dead worker."""
    import jax

    with warnings.catch_warnings():
        if jax.default_backend() == "cpu":
            warnings.filterwarnings(
                "ignore", message=".*fork.*", category=RuntimeWarning
            )
            warnings.filterwarnings(
                "ignore", message=".*fork.*", category=DeprecationWarning
            )
        yield


def _make_pool(n: int) -> Tuple[Any, List[Any]]:
    """Fork a pool of ``n`` workers; returns (pool, worker process snapshot).

    The snapshot keeps references to the ORIGINAL worker ``Process``
    objects: ``Pool`` silently respawns dead workers (mutating its internal
    list), but a respawn never resurrects the task the dead worker was
    running — the original objects' ``exitcode`` is the reliable death
    signal."""
    ctx = mp.get_context("fork")
    pool = ctx.Pool(n)
    return pool, list(getattr(pool, "_pool", []))


def run_partitions_forked(
    pdf: pd.DataFrame,
    schema: Any,
    groups: List[Any],
    map_func: Callable,
    cursor: Any,
    output_schema: Any,
    n_workers: int,
    wrap_df: Callable,
    to_arrow: Callable,
    chunk_timeout: float = 0.0,
    policy: Optional[RetryPolicy] = None,
    injector: Optional[FaultInjector] = None,
    stats: Optional[ResilienceStats] = None,
) -> List[pa.Table]:
    """Run ``map_func`` over every logical partition using a supervised fork
    pool.

    ``groups`` is a list of positional row selections (ndarray or slice),
    one per logical partition, in partition order. Returns the per-partition
    arrow tables in the same order. ``chunk_timeout`` bounds each chunk's
    wall clock (0 = unbounded); ``policy``/``injector``/``stats`` are the
    resilience plumbing (see module docstring) and default to fail-safe
    no-ops.
    """
    policy = policy or RetryPolicy()
    injector = injector or NULL_INJECTOR
    stats = stats or ResilienceStats()
    sizes = [
        (idx.stop - idx.start) if isinstance(idx, slice) else len(idx)
        for idx in groups
    ]
    chunks = split_chunks(sizes, n_workers * 4)

    def _serial(part_ids: Any) -> List[pa.Table]:
        return [
            _exec_partition(
                no, pdf, groups, map_func, cursor, schema, output_schema,
                wrap_df, to_arrow,
            )
            for no in part_ids
        ]

    from ..obs import get_tracer

    tracer = get_tracer()
    # a single chunk gains nothing from a one-worker pool — skip the ~100ms
    # fork/teardown entirely and run in-driver
    if len(chunks) <= 1:
        if not chunks:
            return []
        with tracer.span(
            "map.serial", cat="engine", partitions=len(groups)
        ):
            return _serial(chunks[0])

    with _FORK_LOCK, tracer.span(
        "map.parallel",
        cat="engine",
        chunks=len(chunks),
        workers=n_workers,
        partitions=len(groups),
    ):
        _FORK_STATE.clear()
        _FORK_STATE.update(
            pdf=pdf,
            groups=groups,
            map_func=map_func,
            cursor=cursor,
            schema=schema,
            output_schema=output_schema,
            wrap_df=wrap_df,
            to_arrow=to_arrow,
            injector=injector,
            # children inherit this by fork: worker spans parent onto the
            # driver's map.parallel span so the tree stays connected
            trace_parent=tracer.current_span_id(),
        )
        try:
            with _quiet_fork_warnings():
                results, quarantined, failures = _supervise(
                    chunks, n_workers, chunk_timeout, policy, injector, stats
                )
            # quarantine phase: poison/exhausted chunks degrade to serial
            # in-driver execution, partition by partition, so the failure
            # report pinpoints the exact offending partitions
            report: Dict[int, str] = {}
            for ci in quarantined:
                tables: List[pa.Table] = []
                for no in chunks[ci]:
                    try:
                        tables.append(_serial([no])[0])
                    except Exception as ex:
                        history = "; ".join(failures.get(ci, []))
                        report[no] = (
                            f"{type(ex).__name__}: {ex}"
                            + (f" (pool attempts: {history})" if history else "")
                        )
                results[ci] = tables
                if not any(no in report for no in chunks[ci]):
                    stats.inc("map.serial_fallbacks")
            if report:
                raise ParallelMapError(report)
        finally:
            _FORK_STATE.clear()
    tables_out: List[pa.Table] = []
    for ci in range(len(chunks)):
        tables_out.extend(results[ci])
    return tables_out


def _supervise(
    chunks: List[Any],
    n_workers: int,
    chunk_timeout: float,
    policy: RetryPolicy,
    injector: FaultInjector,
    stats: ResilienceStats,
) -> Tuple[Dict[int, List[pa.Table]], List[int], Dict[int, List[str]]]:
    """Supervised dispatch of ``chunks`` over fork pools.

    Returns ``(results, quarantined_chunk_ids, failure_history)`` where
    ``results`` maps chunk id → decoded per-partition tables for every
    chunk that succeeded in a pool.
    """
    results: Dict[int, List[pa.Table]] = {}
    quarantined: List[int] = []
    failures: Dict[int, List[str]] = {}
    attempts: Dict[int, int] = {ci: 0 for ci in range(len(chunks))}
    pending: deque = deque(range(len(chunks)))

    def fail(ci: int, ex: BaseException) -> None:
        cat = classify_failure(ex)
        if cat is FailureCategory.FATAL:
            raise ex
        attempts[ci] += 1
        failures.setdefault(ci, []).append(
            f"attempt {attempts[ci]} [{cat.value}] {type(ex).__name__}: {ex}"
        )
        if policy.should_retry(cat, attempts[ci]):
            stats.inc("map.chunk_retries")
            pending.append(ci)
        else:
            stats.inc("map.quarantined_chunks")
            stats.inc("map.quarantined_partitions", len(chunks[ci]))
            quarantined.append(ci)

    # hard backstop against pathological requeue loops (e.g. a deadline
    # that keeps evicting collateral chunks): once crossed, everything
    # still pending degrades to the serial quarantine path
    max_waves = (policy.max_attempts + 1) * len(chunks) + 4
    wave = 0
    while pending:
        wave += 1
        if wave > max_waves:
            for ci in pending:
                stats.inc("map.quarantined_chunks")
                stats.inc("map.quarantined_partitions", len(chunks[ci]))
                quarantined.append(ci)
            pending.clear()
            break
        if wave > 1:
            stats.inc("map.pool_rebuilds")
        pool, procs = _make_pool(min(n_workers, len(pending)))
        # in-flight cap == pool size: every dispatched chunk starts on an
        # idle worker immediately, so its deadline measures real run time
        capacity = min(n_workers, len(pending))
        inflight: Dict[int, Tuple[Any, Deadline]] = {}
        try:
            rebuild = False
            while (pending or inflight) and not rebuild:
                while pending and len(inflight) < capacity:
                    ci = pending.popleft()
                    try:
                        # driver-side injection site (synthetic dispatch
                        # errors); `kill` is driver-safe (degrades to raise)
                        injector.fire(SITE_MAP_DISPATCH)
                    except Exception as ex:
                        fail(ci, ex)
                        continue
                    inflight[ci] = (
                        pool.apply_async(_run_chunk, (chunks[ci],)),
                        Deadline.after(chunk_timeout),
                    )
                progressed = False
                for ci in list(inflight):
                    ar, dl = inflight[ci]
                    if ar.ready():
                        del inflight[ci]
                        progressed = True
                        try:
                            results[ci] = _harvest_chunk(ar.get(), stats)
                            stats.inc("map.chunks_ok")
                        except Exception as ex:
                            fail(ci, ex)
                    elif dl.expired:
                        # a pool can't cancel one task — tear down the wave;
                        # only the expired chunk is charged an attempt,
                        # collateral in-flight chunks requeue for free
                        stats.inc("map.deadline_expiries")
                        del inflight[ci]
                        fail(
                            ci,
                            ChunkTimeoutError(
                                f"chunk exceeded {chunk_timeout}s deadline"
                            ),
                        )
                        pending.extend(inflight.keys())
                        inflight.clear()
                        rebuild = True
                        break
                if rebuild:
                    break
                dead = [p for p in procs if p.exitcode is not None]
                if dead:
                    # harvest whatever completed, then charge the chunks
                    # whose results can never arrive (the pool respawns
                    # workers but NOT their lost tasks)
                    stats.inc("map.worker_lost", len(dead))
                    for ci in list(inflight):
                        ar, _ = inflight.pop(ci)
                        if ar.ready():
                            try:
                                results[ci] = _harvest_chunk(ar.get(), stats)
                                stats.inc("map.chunks_ok")
                            except Exception as ex:
                                fail(ci, ex)
                        else:
                            fail(
                                ci,
                                WorkerLostError(
                                    "pool worker died mid-chunk (exitcodes: "
                                    f"{[p.exitcode for p in dead]})"
                                ),
                            )
                    rebuild = True
                    break
                if not progressed:
                    time.sleep(_POLL_INTERVAL)
        finally:
            pool.terminate()
            pool.join()
        if pending and wave < max_waves:
            # backoff before re-forking; seed by wave so concurrent maps
            # don't thunder in lockstep
            time.sleep(min(policy.delay(wave, seed=id(chunks)), 1.0))
    return results, quarantined, failures
