"""The in-tree DAG runner (replaces adagio).

Parity with the reference (`fugue/workflow/_workflow_context.py:19-58`): binds
the execution engine + RPC server + checkpoint path, and runs the task graph
with configurable parallelism (``fugue.workflow.concurrency``). Adds
checkpoint-aware pruning: tasks whose deterministic checkpoint already exists
load from storage and their exclusive ancestors are skipped (true resume).
"""

import contextvars
import time
import uuid as _uuid
from concurrent.futures import FIRST_COMPLETED, Future, ThreadPoolExecutor, wait
from typing import Any, Dict, List, Optional, Set

from ..constants import FUGUE_CONF_WORKFLOW_CONCURRENCY
from ..dataframe import DataFrame
from ..exceptions import FugueWorkflowError, FugueWorkflowRuntimeError
from ..execution.execution_engine import ExecutionEngine
from ..resilience import (
    SITE_TASK_EXECUTE,
    FaultInjector,
    RetryPolicy,
    classify_failure,
)
from ._checkpoint import CheckpointPath, StrongCheckpoint
from ._tasks import FugueTask


class FugueWorkflowContext:
    def __init__(self, execution_engine: ExecutionEngine, conf: Any = None):
        # conf is the RUN-scoped merge (engine conf + workflow conf) when
        # workflow.run builds the context; workflow conf no longer writes
        # through to the engine, so reading engine.conf alone would miss
        # workflow-level fault plans / retry policies
        conf = conf if conf is not None else execution_engine.conf
        self._engine = execution_engine
        self._conf = conf
        self._checkpoint_path = CheckpointPath(execution_engine, conf=conf)
        self._results: Dict[str, DataFrame] = {}
        self._aliases: Dict[int, FugueTask] = {}
        self._removed: Set[int] = set()
        self._cache_plan: Any = None
        self._dist_plan: Any = None
        # fault budgets span the whole run (an injected `error@1` fails one
        # task once, not once per retry attempt)
        self._injector = FaultInjector.from_conf(conf)
        # default 1 attempt = fail fast, the reference behavior; retried
        # attempts re-consult StrongCheckpoint.exists so work that already
        # reached storage replays from disk instead of recomputing
        self._task_policy = RetryPolicy.from_conf(
            conf,
            prefix="fugue.tpu.retry.task",
            default_attempts=1,
        )

    @property
    def execution_engine(self) -> ExecutionEngine:
        return self._engine

    @property
    def checkpoint_path(self) -> CheckpointPath:
        return self._checkpoint_path

    def get_result(self, task: FugueTask) -> DataFrame:
        t = self._aliases.get(id(task), task)
        if id(t) not in self._results and id(task) in self._removed:
            raise FugueWorkflowError(
                "this task's intermediate result was optimized away by the "
                "plan optimizer (fused into a neighbor or repositioned by "
                "filter pushdown); pin it with persist()/checkpoint()/"
                "yield_dataframe_as(), or disable the optimizer with "
                "fugue.tpu.plan.optimize=false"
            )
        plan = getattr(self, "_cache_plan", None)
        if (
            id(t) not in self._results
            and plan is not None
            and id(t) in plan.skipped
        ):
            raise FugueWorkflowError(
                "this task was never executed: a downstream result-cache "
                "hit cut the plan above it (fugue_tpu/cache, docs/cache.md);"
                " pin it with persist()/checkpoint()/yield_dataframe_as() to"
                " keep it addressable, or disable the cache with "
                "fugue.tpu.cache.enabled=false"
            )
        dp = getattr(self, "_dist_plan", None)
        if (
            id(t) not in self._results
            and dp is not None
            and id(t) in dp.interior_ids
        ):
            raise FugueWorkflowError(
                "this task executed REMOTELY as a leased board task inside a "
                "distributed workflow fragment (fugue_tpu/plan/distribute.py,"
                " docs/distributed.md); its intermediate frame never "
                "materialized in this process. Pin it with persist()/"
                "checkpoint()/yield_dataframe_as() to keep it local, or set "
                "fugue.tpu.dist.enabled=false"
            )
        return self._results[id(t)]

    def has_result(self, task: FugueTask) -> bool:
        t = self._aliases.get(id(task), task)
        return id(t) in self._results

    def run(
        self,
        tasks: List[FugueTask],
        result_aliases: Optional[Dict[int, FugueTask]] = None,
        removed_results: Optional[Set[int]] = None,
    ) -> None:
        execution_id = str(_uuid.uuid4())
        # plan-optimizer aliasing: the optimizer may execute CLONES of the
        # compiled tasks (pruned creates, rewired filters, fused chains);
        # get_result resolves an original task to its executed stand-in,
        # and raises a descriptive error for results the rewrites removed
        self._aliases: Dict[int, FugueTask] = result_aliases or {}
        self._removed = removed_results or set()
        self._checkpoint_path.init_temp_path(execution_id)
        # result cache (fugue_tpu/cache): fingerprint the post-optimization
        # DAG, cut it at the deepest cached frontier, and eagerly load the
        # frontier frames; tasks upstream of the cut never run. Disabled
        # (fugue.tpu.cache.enabled=false) this whole block is one boolean
        # check and the run path is byte-for-byte the pre-cache one.
        self._cache_plan = None
        cache = self._engine.result_cache
        if cache.enabled:
            from ..cache import plan_cache

            self._cache_plan = plan_cache(
                tasks, self._engine, cache, self._checkpoint_path
            )
        # distributed-workflow pass (fugue_tpu/plan/distribute.py): with
        # fugue.tpu.dist.board set, distributable fragments route through
        # DistSupervisor.run_workflow_job and their interior tasks never
        # run locally. Planner bugs must never fail a run: any planning
        # error degrades to fully-local execution with a warning.
        self._dist_plan = None
        try:
            from ..plan import plan_distribution

            dp = plan_distribution(tasks, self._conf, self._cache_plan)
            if dp.active and dp.fragments:
                self._dist_plan = dp
        except Exception as ex:  # pragma: no cover - defensive degrade
            self._engine.log.warning(
                "distributed-workflow planning failed (%s: %s); "
                "running fully local",
                type(ex).__name__,
                ex,
            )
        # fan-out map: a ONE-PASS (local unbounded) result consumed by more
        # than one downstream task must be materialized once, or the second
        # consumer would silently read an exhausted stream
        self._consumers: Dict[int, int] = {}
        for t in tasks:
            for d in t.inputs:
                self._consumers[id(d)] = self._consumers.get(id(d), 0) + 1
        from ..obs import get_tracer

        # capture the workflow.run span id on THIS thread: with concurrency
        # > 1 tasks run on pool threads whose span stacks are empty, so the
        # task spans parent onto it explicitly instead of detaching
        self._trace_root = get_tracer().current_span_id()
        # the rpc server's start/stop is REF-COUNTED (RPCHandler._running),
        # so N concurrent runs on one engine share one live server and the
        # last finisher tears it down; the engine-level active-run counter
        # is the occupancy gauge the serving layer's /readyz reports
        rpc_server = self._engine.rpc_server
        rpc_server.start()
        self._engine._run_started()
        try:
            self._run_graph(tasks)
        finally:
            self._engine._run_finished()
            rpc_server.stop()
            self._checkpoint_path.remove_temp_path()

    # ------------------------------------------------------------------
    def _run_graph(self, tasks: List[FugueTask]) -> None:
        """Run every task (insertion order is topological by construction);
        a deterministic-checkpoint hit loads from storage instead of
        executing (reference semantics: set_result replaces the computed
        frame with the stored one — here we shortcut the execute too when
        the task's own inputs are checkpoint hits or absent)."""
        concurrency = self._engine.conf.get(FUGUE_CONF_WORKFLOW_CONCURRENCY, 1)
        plan = getattr(self, "_cache_plan", None)
        cut = set(plan.skipped) if plan is not None else set()
        dp = getattr(self, "_dist_plan", None)
        if dp is not None:
            # fragment interiors execute remotely as leased board map/reduce
            # tasks; locally they are part of the cut (their consumers — the
            # fragment result tasks — are intercepted in _run_task_once)
            cut |= dp.interior_ids
        if concurrency <= 1:
            for t in tasks:
                if id(t) not in cut:
                    self._run_task(t)
            return
        remaining = {id(t): t for t in tasks if id(t) not in cut}
        # skipped tasks count as done so their consumers' readiness checks
        # pass (a consumer that needed them would not have been cut)
        done: Set[int] = set(cut)
        running: Dict[Future, int] = {}
        first_error: List[BaseException] = []
        with ThreadPoolExecutor(max_workers=concurrency) as pool:
            while (remaining or running) and not first_error:
                ready = [
                    t
                    for t in list(remaining.values())
                    if all(id(d) in done for d in t.inputs)
                ]
                for t in ready:
                    del remaining[id(t)]
                    # pool threads have no context of their own: submit
                    # through a context copy so task spans (and anything
                    # they fork) keep the run-attribution labels
                    running[
                        pool.submit(
                            contextvars.copy_context().run, self._run_task, t
                        )
                    ] = id(t)
                if not running:
                    if remaining:
                        raise FugueWorkflowRuntimeError("workflow graph has a cycle")
                    break
                finished, _ = wait(list(running.keys()), return_when=FIRST_COMPLETED)
                for f in finished:
                    tid = running.pop(f)
                    exc = f.exception()
                    if exc is not None:
                        first_error.append(exc)
                    else:
                        done.add(tid)
        if first_error:
            raise first_error[0]

    def _run_task(self, task: FugueTask) -> None:
        """One task under the per-task retry policy (``fugue.tpu.retry.task.*``).

        Each attempt starts by re-consulting the task's deterministic
        StrongCheckpoint: work that reached storage on a previous attempt
        (or a previous RUN — checkpoint files are uuid-keyed and permanent)
        replays from disk instead of recomputing. Deterministic (POISON)
        failures are never retried — the same inputs would fail the same
        way."""
        from ..obs import get_tracer

        policy = self._task_policy
        attempts = 0
        with get_tracer().span(
            "workflow.task",
            cat="workflow",
            annotate=True,
            parent=getattr(self, "_trace_root", None),
            task=task.name or type(task).__name__,
        ) as sp:
            while True:
                try:
                    self._run_task_once(task)
                    sp.set(attempts=attempts + 1)
                    return
                except Exception as ex:
                    cat = classify_failure(ex)
                    attempts += 1
                    if not policy.should_retry(cat, attempts):
                        sp.set(attempts=attempts)
                        if task.defined_at and hasattr(ex, "add_note"):
                            ex.add_note(
                                f"[fugue-tpu] failing task defined at {task.defined_at}"
                            )
                        raise
                    self._engine.resilience_stats.inc("workflow.task_retries")
                    self._engine.log.warning(
                        "task %s failed with %s [%s]; retry %d/%d",
                        task.name or type(task).__name__,
                        type(ex).__name__,
                        cat.value,
                        attempts,
                        policy.max_attempts - 1,
                    )
                    time.sleep(policy.delay(attempts, seed=task.__uuid__()))

    def _run_task_once(self, task: FugueTask) -> None:
        from ..obs import get_tracer

        tid = task.__uuid__()
        plan = getattr(self, "_cache_plan", None)
        cp = task.checkpoint
        if isinstance(cp, StrongCheckpoint):
            cp.set_id(tid)
            if cp.exists(self._checkpoint_path, tid):
                self._engine.resilience_stats.inc("workflow.checkpoint_replays")
                with get_tracer().span(
                    "task.checkpoint_replay", cat="workflow", task_uuid=tid
                ):
                    df = cp.load(self._checkpoint_path)
                    if task.broadcast_flag:
                        df = self._engine.broadcast(df)
                    if task.yield_dataframe_handler is not None:
                        task.yield_dataframe_handler(df)
                    self._results[id(task)] = df
                # one artifact, two indexes: the replayed checkpoint file
                # gets a cache ref so future runs also cut ABOVE this task
                self._maybe_cache_publish(task, df)
                return
        if plan is not None and id(task) in plan.hits:
            # served from the result cache: the frame is already loaded
            # (plan time); checkpoint/broadcast/yield contracts still run
            with get_tracer().span(
                "task.cache_hit",
                cat="cache",
                task_uuid=tid,
                tier=plan.hit_tier.get(id(task), ""),
            ):
                result = task.set_result(self, plan.hits[id(task)])
                self._results[id(task)] = result
            return
        if plan is not None and id(task) in plan.delta_hits:
            # partition-level delta recompute (fugue_tpu/cache/delta.py):
            # cached partitions were eager-loaded at plan time; only the
            # NEW partitions stream through the chain here, then merge
            from ..cache.delta import execute_delta

            hit = plan.delta_hits[id(task)]
            with get_tracer().span(
                "task.delta_recompute",
                cat="cache",
                task_uuid=tid,
                partitions=f"{hit.matched_parts}/{hit.total_parts}",
                bytes_skipped=hit.bytes_matched,
            ):
                df = execute_delta(self, task, hit)
                result = task.set_result(self, df)
                self._results[id(task)] = result
            # publishes the MERGED result under the new full fingerprint
            # (a later exact-match run takes the whole-task fast path) and
            # appends the fresh segment / partial to the manifest
            self._maybe_cache_publish(task, result, delta_hit=hit)
            return
        dp = getattr(self, "_dist_plan", None)
        if dp is not None and id(task) in dp.results:
            # fragment result: the whole covered subgraph (loads, row-local
            # chains, shuffle, terminal, tail) ran as leased board tasks
            # under the dist recovery ladder; only the combined frame lands
            # here. The result still flows through set_result so the
            # checkpoint/broadcast/yield contracts — and the cache publish
            # below — behave exactly as a locally-computed frame would.
            from ..plan import execute_fragment

            frag = dp.results[id(task)]
            with get_tracer().span(
                "dist.workflow_fragment",
                cat="dist",
                task=task.name or type(task.extension).__name__,
                keys=",".join(frag.keys),
                buckets=frag.buckets,
            ):
                pdf = execute_fragment(frag, self._engine, self._conf)
                df = self._engine.to_df(pdf)
                result = task.set_result(self, df)
                self._results[id(task)] = result
            self._maybe_cache_publish(task, result)
            return
        inputs = [self._results[id(d)] for d in task.inputs]
        self._injector.fire(SITE_TASK_EXECUTE)
        result = task.execute(self, inputs)
        if result is not None:
            result = task.set_result(self, result)
            if (
                getattr(self, "_consumers", {}).get(id(task), 0) > 1
                and result.is_local
                and not result.is_bounded
            ):
                # stream results stay lazy for single consumers (the
                # out-of-core contract); a fan-out forces one host-side
                # materialization so every consumer sees all rows
                result = result.as_local_bounded()
            self._results[id(task)] = result
            self._maybe_cache_publish(task, result, inputs=inputs)

    def _maybe_cache_publish(
        self,
        task: FugueTask,
        result: DataFrame,
        inputs: Optional[List[DataFrame]] = None,
        delta_hit: Any = None,
    ) -> None:
        """Publish a finished (bounded) result under its plan fingerprint.
        A permanent StrongCheckpoint file is indexed by reference instead
        of re-written — the cache never holds a second copy of an artifact
        the checkpoint publisher already owns. Delta-eligible tasks
        (``fugue_tpu/cache/delta.py``) additionally maintain their source
        partition manifest so the NEXT grown-source run recomputes only
        its delta."""
        plan = getattr(self, "_cache_plan", None)
        if plan is None:
            return
        fp = plan.fp(task)
        if fp is None:
            return
        if result.is_local and not result.is_bounded:
            return  # publishing would consume a one-pass stream
        from ..obs import get_tracer

        ref = None
        cp = task.checkpoint
        if (
            isinstance(cp, StrongCheckpoint)
            and cp.storage_type == "file"
            and cp.permanent
        ):
            try:
                ref = cp._file_path(self._checkpoint_path)
            except Exception:
                ref = None
        with get_tracer().span(
            "cache.publish",
            cat="cache",
            task=task.name or type(task.extension).__name__,
            fp=fp[:12],
        ) as sp:
            info = self._engine.result_cache.publish(
                fp, result, self._engine, str(result.schema), ref_path=ref
            )
            sp.set(**info)
        from ..cache.delta import publish_manifest_after

        publish_manifest_after(self, task, result, inputs=inputs, hit=delta_hit)
