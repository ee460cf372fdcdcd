"""The engine contract: ExecutionEngine + MapEngine + SQLEngine.

Parity with the reference (`fugue/execution/execution_engine.py`):

- ``FugueEngineBase`` (``:92``): to_df/log/conf
- ``EngineFacet`` (``:143``): sub-engine bound to a parent engine
- ``SQLEngine`` (``:183``): SQL over named frames
- ``MapEngine`` (``:277``): ``map_dataframe`` — THE distributed primitive
- ``ExecutionEngine`` (``:338``): physical ops + derived ops + context
  management + the zip/comap co-partition protocol (``:962-1111``)

TPU-first redesigns vs the reference:
- derived ``select/filter/assign/aggregate`` default to the column-IR
  evaluators instead of generated-SQL (SQL engines may override);
- the zip/comap wire format is arrow IPC (columnar), not pickle blobs;
- engine context uses ``contextvars`` for thread/async safety (same
  semantics as reference ``:1182-1212``).
"""

import logging
from abc import ABC, abstractmethod
from contextlib import contextmanager
from contextvars import ContextVar
from threading import RLock
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional, Union

from .._utils.assertion import assert_or_throw
from .._utils.hash import to_uuid
from .._utils.params import ParamDict
from ..collections.partition import (
    EMPTY_PARTITION_SPEC,
    PartitionCursor,
    PartitionSpec,
)
from ..collections.sql import StructuredRawSQL
from ..collections.yielded import PhysicalYielded, Yielded
from ..column import ColumnExpr, SelectColumns
from ..constants import _FUGUE_GLOBAL_CONF
from ..dataframe import (
    AnySchema,
    ArrayDataFrame,
    DataFrame,
    DataFrames,
    LocalBoundedDataFrame,
    LocalDataFrame,
    YieldedDataFrame,
    deserialize_df,
    get_join_schemas,
    serialize_df,
)
from ..dataframe.utils import get_temp_df_path
from ..exceptions import FugueBug, FugueInvalidOperation
from ..schema import Schema

_FUGUE_BLOB_PREFIX = "__fugue_blob_"

_CONTEXT_ENGINE: ContextVar[Optional["ExecutionEngine"]] = ContextVar(
    "fugue_tpu_execution_engine", default=None
)
_GLOBAL_ENGINE_LOCK = RLock()
_GLOBAL_ENGINE: List[Optional["ExecutionEngine"]] = [None]

# run-scoped conf overlays (docs/serving.md "Per-run conf scoping"):
# ``workflow.run`` used to write workflow conf into the shared engine's
# conf dict, where it leaked into every later run on the same engine.
# Instead each run enters ``engine.run_conf_scope(overlay)``, which binds
# a merged base+overlay view to THIS context only; ``engine.conf`` reads
# resolve through it. Context-local, so concurrent runs on one engine
# each see their own conf; task threads (copy_context in
# _workflow_context) and fork workers inherit the scope, exactly like
# run_labels. The list holds (engine id, merged view) pairs so nested
# runs on DIFFERENT engines don't shadow each other's overlays.
_RUN_CONF: ContextVar[tuple] = ContextVar("fugue_tpu_run_conf", default=())


class FugueEngineBase(ABC):
    @property
    @abstractmethod
    def conf(self) -> ParamDict:
        raise NotImplementedError

    @property
    @abstractmethod
    def log(self) -> logging.Logger:
        raise NotImplementedError

    @property
    @abstractmethod
    def is_distributed(self) -> bool:
        raise NotImplementedError

    @abstractmethod
    def to_df(self, df: Any, schema: Any = None) -> DataFrame:
        raise NotImplementedError


class EngineFacet(FugueEngineBase):
    """A sub-engine bound to a parent ExecutionEngine (reference ``:143``)."""

    def __init__(self, execution_engine: "ExecutionEngine"):
        self._execution_engine = execution_engine

    @property
    def execution_engine(self) -> "ExecutionEngine":
        return self._execution_engine

    @property
    def conf(self) -> ParamDict:
        return self._execution_engine.conf

    @property
    def log(self) -> logging.Logger:
        return self._execution_engine.log

    def to_df(self, df: Any, schema: Any = None) -> DataFrame:
        return self._execution_engine.to_df(df, schema)

    @property
    def execution_engine_constraint(self) -> type:
        """The engine type this facet requires (for set_sql_engine checks)."""
        return ExecutionEngine


class SQLEngine(EngineFacet):
    """SQL execution over a dict of named DataFrames (reference ``:183``)."""

    @property
    def dialect(self) -> Optional[str]:
        return None

    def encode_name(self, name: str) -> str:
        return name

    @abstractmethod
    def select(self, dfs: DataFrames, statement: StructuredRawSQL) -> DataFrame:
        raise NotImplementedError

    def table_exists(self, table: str) -> bool:
        raise NotImplementedError(f"{type(self)} doesn't support tables")

    def save_table(
        self,
        df: DataFrame,
        table: str,
        mode: str = "overwrite",
        partition_spec: Optional[PartitionSpec] = None,
        **kwargs: Any,
    ) -> None:
        raise NotImplementedError(f"{type(self)} doesn't support tables")

    def load_table(self, table: str, **kwargs: Any) -> DataFrame:
        raise NotImplementedError(f"{type(self)} doesn't support tables")


class MapEngine(EngineFacet):
    """Per-partition mapping — THE distributed primitive (reference ``:277``)."""

    @abstractmethod
    def map_dataframe(
        self,
        df: DataFrame,
        map_func: Callable[[PartitionCursor, LocalDataFrame], LocalDataFrame],
        output_schema: Any,
        partition_spec: PartitionSpec,
        on_init: Optional[Callable[[int, DataFrame], Any]] = None,
        map_func_format_hint: Optional[str] = None,
    ) -> DataFrame:
        raise NotImplementedError

    def map_bag(
        self,
        bag: Any,
        map_func: Callable,
        partition_spec: PartitionSpec,
        on_init: Optional[Callable] = None,
    ) -> Any:
        raise NotImplementedError(f"{type(self)} doesn't support bags")


class ExecutionEngine(FugueEngineBase):
    """The backend contract every engine implements (reference ``:338``)."""

    def __init__(self, conf: Any = None):
        _conf = ParamDict(conf)
        self._conf = ParamDict(_FUGUE_GLOBAL_CONF)
        self._conf.update(_conf)
        self._rlock = RLock()
        self._map_engine: Optional[MapEngine] = None
        self._sql_engine: Optional[SQLEngine] = None
        self._stopped = False
        self._ctx_count = 0
        self._is_global = False
        self._compile_conf = ParamDict()
        self._rpc_server: Any = None
        self._resilience_stats: Any = None
        self._plan_stats: Any = None
        self._analysis_stats: Any = None
        self._tuner: Any = None
        self._metrics: Any = None
        self._active_runs = 0
        # apply trace switches (fugue.tpu.trace.* / FUGUE_TPU_TRACE) so
        # constructing an engine with tracing conf turns the tracer on
        from ..obs import (
            configure_events_from_conf,
            configure_from_conf,
            configure_sampler_from_conf,
        )

        configure_from_conf(self._conf)
        # ditto for the continuous resource sampler (fugue.tpu.telemetry.*
        # / FUGUE_TPU_TELEMETRY), plus this engine's occupancy probes
        configure_sampler_from_conf(self._conf)
        # and the cluster flight recorder (fugue.tpu.events.*)
        configure_events_from_conf(self._conf)
        self._register_resource_probes()

    def __repr__(self) -> str:
        return f"{type(self).__name__}"

    @property
    def conf(self) -> ParamDict:
        scopes = _RUN_CONF.get()
        if scopes:
            me = id(self)
            for eng_id, view in reversed(scopes):
                if eng_id == me:
                    return view
        return self._conf

    @property
    def base_conf(self) -> ParamDict:
        """The engine-level conf dict itself, ignoring any active
        run-scope overlay — what a deliberate engine-global write should
        target, and what run-scope leak tests assert against."""
        return self._conf

    @contextmanager
    def run_conf_scope(self, overlay: Any = None) -> Iterator[ParamDict]:
        """Bind ``overlay`` over this engine's conf for the current
        context only (and everything it forks via ``copy_context`` /
        ``fork``). Reads through ``engine.conf`` resolve overlay-first;
        writes land in the scoped view and vanish at exit — a run can no
        longer mutate a shared engine's conf. Nestable; inner scopes see
        outer overlays (merged at entry)."""
        if not overlay:
            yield self.conf
            return
        merged = ParamDict(self.conf)  # current view: nested scopes stack
        merged.update(overlay)
        scopes = _RUN_CONF.get()
        token = _RUN_CONF.set(scopes + ((id(self), merged),))
        try:
            yield merged
        finally:
            _RUN_CONF.reset(token)

    @property
    def log(self) -> logging.Logger:
        return logging.getLogger(type(self).__name__)

    # ---- sub-engines ------------------------------------------------------
    @abstractmethod
    def create_default_map_engine(self) -> MapEngine:
        raise NotImplementedError

    @abstractmethod
    def create_default_sql_engine(self) -> SQLEngine:
        raise NotImplementedError

    @property
    def map_engine(self) -> MapEngine:
        # lazy singletons double-checked under the engine lock (ISSUE 10
        # audit): two concurrent sessions' first touch must not build two
        # sub-engines and split state between them
        if self._map_engine is None:
            with self._rlock:
                if self._map_engine is None:
                    self._map_engine = self.create_default_map_engine()
        return self._map_engine

    @property
    def sql_engine(self) -> SQLEngine:
        if self._sql_engine is None:
            with self._rlock:
                if self._sql_engine is None:
                    self._sql_engine = self.create_default_sql_engine()
        return self._sql_engine

    def set_sql_engine(self, engine: "SQLEngine") -> None:
        assert_or_throw(
            isinstance(self, engine.execution_engine_constraint),
            lambda: FugueInvalidOperation(
                f"{type(engine)} requires {engine.execution_engine_constraint}"
            ),
        )
        with self._rlock:
            self._sql_engine = engine

    # ---- context management (reference :50-89, 362-421, 1182-1212) -------
    @property
    def in_context(self) -> bool:
        return self._ctx_count > 0

    @property
    def is_global(self) -> bool:
        return self._is_global

    @contextmanager
    def _as_context(self) -> Iterator["ExecutionEngine"]:
        with self._rlock:
            self._ctx_count += 1
        token = _CONTEXT_ENGINE.set(self)
        try:
            yield self
        finally:
            _CONTEXT_ENGINE.reset(token)
            with self._rlock:
                self._ctx_count -= 1
                if self._ctx_count == 0 and not self._is_global:
                    self.stop()

    @contextmanager
    def _as_borrowed_context(self) -> Iterator["ExecutionEngine"]:
        """Set as the context engine WITHOUT stop-on-last-exit ownership.

        Workflow runs BORROW the engine: the reference's ``dag.run(engine)``
        never stops a user-held engine (no as_context in
        `/root/reference/fugue/workflow/workflow.py`), so the same engine
        instance can run many workflows. Stop-on-exit remains the contract
        of the explicit ``engine_context``/``as_context`` API only."""
        with self._rlock:
            self._ctx_count += 1
        token = _CONTEXT_ENGINE.set(self)
        try:
            yield self
        finally:
            _CONTEXT_ENGINE.reset(token)
            with self._rlock:
                self._ctx_count -= 1

    def set_global(self) -> "ExecutionEngine":
        # lock order matches stop(): the module-wide global-engine lock
        # first, then the engine's own rlock for its shared flag
        with _GLOBAL_ENGINE_LOCK:
            old = _GLOBAL_ENGINE[0]
            if old is not None and old is not self:
                with old._rlock:
                    old._is_global = False
                if not old.in_context:
                    old.stop()
            with self._rlock:
                self._is_global = True
            _GLOBAL_ENGINE[0] = self
        return self

    @staticmethod
    def clear_global() -> None:
        with _GLOBAL_ENGINE_LOCK:
            old = _GLOBAL_ENGINE[0]
            if old is not None:
                old._is_global = False
                if not old.in_context:
                    old.stop()
            _GLOBAL_ENGINE[0] = None

    def stop(self) -> None:
        with self._rlock:
            if not self._stopped:
                self._stopped = True
                self.stop_engine()

    def stop_engine(self) -> None:
        """Subclass hook for resource cleanup."""

    # ---- concurrent-run accounting (ISSUE 10) -----------------------------
    @property
    def active_runs(self) -> int:
        """How many ``workflow.run`` graphs are executing on this engine
        RIGHT NOW — the serving layer's readiness/occupancy gauge."""
        with self._rlock:
            return self._active_runs

    def _run_started(self) -> None:
        with self._rlock:
            self._active_runs += 1

    def _run_finished(self) -> None:
        with self._rlock:
            self._active_runs = max(0, self._active_runs - 1)

    # ---- rpc server binding (set by workflow context) ---------------------
    @property
    def rpc_server(self) -> Any:
        if self._rpc_server is None:
            with self._rlock:
                if self._rpc_server is None:
                    from ..rpc.base import make_rpc_server

                    # conf-driven: "fugue.rpc.server" names the server class
                    # (reference fugue/rpc/base.py:268); default is in-process
                    server = make_rpc_server(self.conf)
                    self._bind_rpc_metrics(server)
                    self._rpc_server = server
        return self._rpc_server

    def set_rpc_server(self, server: Any) -> None:
        with self._rlock:
            self._rpc_server = server
        self._bind_rpc_metrics(server)

    def _bind_rpc_metrics(self, server: Any) -> None:
        # a server with exposure endpoints (HttpRPCServer's /metrics,
        # /healthz, /stats) scrapes THIS engine's registry
        if hasattr(server, "bind_engine"):
            server.bind_engine(self)

    # ---- observability ----------------------------------------------------
    @property
    def metrics(self) -> Any:
        """The engine's :class:`~fugue_tpu.obs.MetricsRegistry` — one
        surface over every stats object (resilience on all engines;
        pipeline + jit_cache on the jax engine). The legacy
        ``engine.*_stats`` attributes delegate to the same objects."""
        if self._metrics is None:
            with self._rlock:
                if self._metrics is None:
                    from ..obs import MetricsRegistry, get_sampler, get_span_metrics

                    reg = MetricsRegistry()
                    reg.register("resilience", lambda: self.resilience_stats)
                    reg.register("plan", lambda: self.plan_stats)
                    reg.register("analysis", lambda: self.analysis_stats)
                    reg.register("cache", lambda: self.result_cache.stats)
                    reg.register("tuning", lambda: self.tuner)
                    # distribution + resource sources are process-global (like
                    # the tracer feeding them) but mounted here so
                    # engine.stats() carries them and engine.reset_stats()
                    # resets them under the keep-entries contract (series/
                    # probes stay registered, observations/ring zero)
                    reg.register("latency", get_span_metrics)
                    reg.register("telemetry", get_sampler)
                    self._metrics = reg
        return self._metrics

    def _register_resource_probes(self) -> None:
        """Register this engine's occupancy probes on the global resource
        sampler. Probes bind through a ``weakref`` — once the engine is
        collected they raise :class:`~fugue_tpu.obs.sampler.ProbeGone`
        and the sampler drops them; a newer engine's registration under
        the same name simply replaces an older one's."""
        import weakref

        from ..obs import get_sampler

        ref = weakref.ref(self)

        def _bound(fn: Callable[["ExecutionEngine"], float]) -> Callable[[], float]:
            def probe() -> float:
                from ..obs.sampler import ProbeGone

                e = ref()
                if e is None:
                    raise ProbeGone()
                return fn(e)

            return probe

        sampler = get_sampler()
        for name, fn in self._resource_probe_fns().items():
            sampler.register_probe(name, _bound(fn))

    def _resource_probe_fns(self) -> Dict[str, Callable[["ExecutionEngine"], float]]:
        """Name → (engine → value) probe map; subclasses extend. Probes
        must guard lazily-created attributes — they run later, on the
        sampler thread, and must never force creation (reading occupancy
        should not allocate the thing it measures)."""

        def _rc(attr: str) -> Callable[["ExecutionEngine"], float]:
            def fn(e: "ExecutionEngine") -> float:
                rc = getattr(e, "_result_cache", None)
                return float(getattr(rc.mem, attr)) if rc is not None else 0.0

            return fn

        return {
            "result_cache_mem_bytes": _rc("bytes"),
            "result_cache_mem_entries": _rc("entries"),
        }

    def stats(self) -> Dict[str, Any]:
        """All registered stats as one dict — the unified replacement for
        reading ``pipeline_stats`` / ``jit_cache_stats`` /
        ``resilience_stats`` separately."""
        return self.metrics.as_dict()

    def reset_stats(self) -> None:
        """Reset every registered stats source (consistent semantics:
        counters to zero; entries kept — the jit cache keeps its compiled
        entries, histogram families keep their registered series, the
        sampler keeps its probes and keeps running; only the recorded
        observations/ring samples zero)."""
        self.metrics.reset()

    def report(self, top_n: int = 15) -> str:
        """Plain-text observability report: top-N spans by total wall from
        the global tracer — with p50/p95/p99 columns from the span-latency
        histograms — plus this engine's metrics."""
        from ..obs import get_span_metrics, get_tracer, render_report

        return render_report(
            get_tracer().records(),
            self.stats(),
            top_n=top_n,
            span_metrics=get_span_metrics(),
        )

    @property
    def resilience_stats(self) -> Any:
        """Structured recovery counters (``fugue_tpu.resilience``): every
        retry, quarantine and fallback on this engine increments one — the
        graceful-degradation machinery is observable, never silent.

        Kept as a stable alias of ``engine.metrics.get("resilience")`` —
        prefer ``engine.stats()["resilience"]`` for reads."""
        if self._resilience_stats is None:
            with self._rlock:
                if self._resilience_stats is None:
                    from ..resilience import ResilienceStats

                    self._resilience_stats = ResilienceStats()
        return self._resilience_stats

    @property
    def plan_stats(self) -> Any:
        """Cumulative logical-plan-optimizer counters for workflows run on
        this engine (cols_pruned / filters_pushed / verbs_fused /
        bytes_skipped). Alias of ``engine.metrics.get("plan")`` — prefer
        ``engine.stats()["plan"]`` for reads."""
        if getattr(self, "_plan_stats", None) is None:
            with self._rlock:
                if getattr(self, "_plan_stats", None) is None:
                    from ..plan import PlanStats

                    self._plan_stats = PlanStats()
        return self._plan_stats

    @property
    def analysis_stats(self) -> Any:
        """Cumulative UDF static-analyzer counters for workflows run on
        this engine (``fugue_tpu/analysis``, docs/analysis.md):
        udfs_analyzed / udfs_translated / udfs_refused by canonical
        reason code. Alias of ``engine.metrics.get("analysis")`` — prefer
        ``engine.stats()["analysis"]`` for reads."""
        if getattr(self, "_analysis_stats", None) is None:
            with self._rlock:
                if getattr(self, "_analysis_stats", None) is None:
                    from ..analysis import AnalysisStats

                    self._analysis_stats = AnalysisStats()
        return self._analysis_stats

    @property
    def tuner(self) -> Any:
        """This engine's :class:`~fugue_tpu.tuning.Tuner` — cost-based
        adaptive execution (``fugue_tpu/tuning``, docs/tuning.md): stream
        chunk size / prefetch depth, shuffle bucket sizing and join-side
        estimates learned from the engine's own telemetry, keyed by plan
        fingerprint and persisted across restarts. Decisions and counters
        live in ``engine.stats()["tuning"]``; ``engine.reset_stats()``
        zeroes counters without forgetting learned settings."""
        if getattr(self, "_tuner", None) is None:
            with self._rlock:
                if getattr(self, "_tuner", None) is None:
                    from ..tuning import Tuner

                    self._tuner = Tuner(self.conf)
        return self._tuner

    @property
    def result_cache(self) -> Any:
        """This engine's :class:`~fugue_tpu.cache.ResultCache` — the
        cross-run memoization layer (``fugue_tpu/cache``, docs/cache.md).
        The memory tier is scoped to this engine (device frames are laid
        out for its mesh); the disk tier is shared by every engine whose
        conf points at the same ``fugue.tpu.cache.dir``. Counters live in
        ``engine.stats()["cache"]``; ``engine.reset_stats()`` zeroes them
        without evicting entries (the ``JitCache.reset`` contract)."""
        if getattr(self, "_result_cache", None) is None:
            with self._rlock:
                if getattr(self, "_result_cache", None) is None:
                    from ..cache import ResultCache

                    self._result_cache = ResultCache(self.conf, log=self.log)
        return self._result_cache

    # ---- physical ops (abstract) ------------------------------------------
    @abstractmethod
    def get_current_parallelism(self) -> int:
        raise NotImplementedError

    @abstractmethod
    def repartition(self, df: DataFrame, partition_spec: PartitionSpec) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def broadcast(self, df: DataFrame) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def persist(
        self,
        df: DataFrame,
        lazy: bool = False,
        **kwargs: Any,
    ) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def join(
        self,
        df1: DataFrame,
        df2: DataFrame,
        how: str,
        on: Optional[List[str]] = None,
    ) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def union(self, df1: DataFrame, df2: DataFrame, distinct: bool = True) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def subtract(
        self, df1: DataFrame, df2: DataFrame, distinct: bool = True
    ) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def intersect(
        self, df1: DataFrame, df2: DataFrame, distinct: bool = True
    ) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def distinct(self, df: DataFrame) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def dropna(
        self,
        df: DataFrame,
        how: str = "any",
        thresh: Optional[int] = None,
        subset: Optional[List[str]] = None,
    ) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def fillna(self, df: DataFrame, value: Any, subset: Optional[List[str]] = None) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def sample(
        self,
        df: DataFrame,
        n: Optional[int] = None,
        frac: Optional[float] = None,
        replace: bool = False,
        seed: Optional[int] = None,
    ) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def take(
        self,
        df: DataFrame,
        n: int,
        presort: str,
        na_position: str = "last",
        partition_spec: Optional[PartitionSpec] = None,
    ) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def load_df(
        self,
        path: Union[str, List[str]],
        format_hint: Any = None,
        columns: Any = None,
        **kwargs: Any,
    ) -> DataFrame:
        raise NotImplementedError

    @abstractmethod
    def save_df(
        self,
        df: DataFrame,
        path: str,
        format_hint: Any = None,
        mode: str = "overwrite",
        partition_spec: Optional[PartitionSpec] = None,
        force_single: bool = False,
        **kwargs: Any,
    ) -> DataFrame:
        raise NotImplementedError

    # ---- derived ops (reference :736-939), IR-evaluated by default --------
    def select(
        self,
        df: DataFrame,
        cols: SelectColumns,
        where: Optional[ColumnExpr] = None,
        having: Optional[ColumnExpr] = None,
    ) -> DataFrame:
        from ..column.eval import eval_select

        local = self.to_df(df).as_local_bounded()
        res = eval_select(local.as_pandas(), local.schema, cols, where, having)
        schema = cols.replace_wildcard(local.schema).infer_schema(local.schema)
        from ..dataframe import PandasDataFrame

        out = PandasDataFrame(res, schema)
        return self.to_df(out)

    def filter(self, df: DataFrame, condition: ColumnExpr) -> DataFrame:
        from ..column import all_cols

        return self.select(df, SelectColumns(all_cols()), where=condition)

    def assign(self, df: DataFrame, columns: List[ColumnExpr]) -> DataFrame:
        """Update or add columns (reference ``:859``)."""
        from ..column import all_cols, col

        assert_or_throw(
            all(c.output_name != "" for c in columns),
            FugueInvalidOperation("all assignments must have output names"),
        )
        existing = df.schema.names
        replaced = {c.output_name: c for c in columns}
        sel: List[ColumnExpr] = []
        for name in existing:
            # replaced columns take the NEW expression's type (reference
            # ``:868``: assigning a constant may change the column type)
            sel.append(replaced.pop(name) if name in replaced else col(name))
        sel.extend(replaced.values())
        return self.select(df, SelectColumns(*sel))

    def fused_apply(self, df: DataFrame, steps: List[Any]) -> DataFrame:
        """Execute a fused chain of row-local verbs (see
        ``fugue_tpu/plan/fused.py``). The default interprets the steps
        sequentially with this engine's own verbs — bit-identical to the
        unfused task chain; engines may override with a compiled
        single-step implementation."""
        from ..plan.fused import apply_steps_engine

        return apply_steps_engine(self, df, steps)

    def lowered_segment(
        self,
        dfs: List[DataFrame],
        steps: List[Any],
        terminal: Any,
        partition_spec: Optional[PartitionSpec],
        fingerprint: str = "",
    ) -> DataFrame:
        """Execute a device-resident plan segment (see
        ``fugue_tpu/plan/lowering.py``): a row-local verb chain flowing
        into a terminal aggregate / take / distinct / join. The default
        interprets the segment per-verb — ``fused_apply`` then the
        terminal with this engine's own verb, exactly what the unlowered
        task pair runs; the jax engine overrides with a single compiled
        SPMD program and falls back here on any lowering refusal."""
        from ..plan.lowering import apply_terminal_engine

        return apply_terminal_engine(
            self, dfs, steps, tuple(terminal), partition_spec
        )

    def aggregate(
        self,
        df: DataFrame,
        partition_spec: Optional[PartitionSpec],
        agg_cols: List[ColumnExpr],
    ) -> DataFrame:
        from ..column import col
        from ..column.functions import is_agg

        assert_or_throw(len(agg_cols) > 0, FugueInvalidOperation("agg_cols is empty"))
        assert_or_throw(
            all(is_agg(c) for c in agg_cols),
            FugueInvalidOperation("all agg_cols must contain aggregation"),
        )
        keys: List[ColumnExpr] = []
        if partition_spec is not None and len(partition_spec.partition_by) > 0:
            keys = [col(k) for k in partition_spec.partition_by]
        return self.select(df, SelectColumns(*keys, *agg_cols))

    # ---- zip/comap: the co-partition protocol (reference :962-1111) ------
    def zip(
        self,
        dfs: DataFrames,
        how: str = "inner",
        partition_spec: Optional[PartitionSpec] = None,
        temp_path: Optional[str] = None,
        to_file_threshold: int = -1,
    ) -> DataFrame:
        """Co-partition multiple frames into one serialized frame.

        Each logical partition of each input serializes into an arrow IPC
        blob row; rows from all inputs union into one frame whose metadata
        carries the per-input schemas (redesign of reference ``:962-1057``).
        """
        assert_or_throw(len(dfs) > 0, FugueInvalidOperation("dfs is empty"))
        how = how.lower()
        assert_or_throw(
            how in ("inner", "left_outer", "right_outer", "full_outer", "cross"),
            lambda: FugueInvalidOperation(f"invalid zip type {how}"),
        )
        spec = partition_spec or EMPTY_PARTITION_SPEC
        keys = list(spec.partition_by)
        if how == "cross":
            assert_or_throw(
                len(keys) == 0, FugueInvalidOperation("cross zip can't have keys")
            )
        elif len(keys) == 0:
            # infer keys: intersection of all schemas
            keys = [
                n
                for n in dfs[0].schema.names
                if all(n in d.schema for d in dfs.values())
            ]
            assert_or_throw(
                len(keys) > 0,
                FugueInvalidOperation("can't infer zip keys: no common columns"),
            )
        serialized: List[DataFrame] = []
        schemas: List[str] = []
        names: List[str] = []
        n = len(dfs)
        for i, (name, df) in enumerate(dfs.items()):
            dfs_keys = [k for k in keys]
            sub_spec = PartitionSpec(spec, by=dfs_keys) if len(keys) > 0 else PartitionSpec()
            sdf = self._serialize_by_partition(
                df,
                sub_spec,
                df_index=i,
                df_count=n,
                temp_path=temp_path,
                to_file_threshold=to_file_threshold,
            )
            serialized.append(sdf)
            schemas.append(str(df.schema))
            names.append(name)
        res = serialized[0]
        for s in serialized[1:]:
            res = self.union(res, s, distinct=False)
        res.reset_metadata(
            {
                "serialized": True,
                "serialized_cols": [f"{_FUGUE_BLOB_PREFIX}{i}" for i in range(n)],
                "schemas": schemas,
                "serialized_has_name": dfs.has_key,
                "names": names,
                "how": how,
                "keys": keys,
            }
        )
        return res

    def _serialize_by_partition(
        self,
        df: DataFrame,
        partition_spec: PartitionSpec,
        df_index: int,
        df_count: int,
        temp_path: Optional[str] = None,
        to_file_threshold: int = -1,
    ) -> DataFrame:
        keys = list(partition_spec.partition_by)
        key_schema = df.schema.extract(keys) if len(keys) > 0 else Schema()
        blob_fields = ",".join(
            f"{_FUGUE_BLOB_PREFIX}{i}:binary" for i in range(df_count)
        )
        out_schema = (
            Schema(str(key_schema) + "," + blob_fields)
            if len(keys) > 0
            else Schema(blob_fields)
        )
        serializer = _PartitionSerializer(
            df_index, df_count, keys, temp_path, to_file_threshold
        )
        return self.map_engine.map_dataframe(
            df, serializer.run, out_schema, partition_spec
        )

    def comap(
        self,
        df: DataFrame,
        map_func: Callable[[PartitionCursor, DataFrames], LocalDataFrame],
        output_schema: Any,
        partition_spec: Optional[PartitionSpec] = None,
        on_init: Optional[Callable[[int, DataFrames], Any]] = None,
    ) -> DataFrame:
        """Apply a function over co-partitioned (zipped) groups
        (reference ``:1059-1111``)."""
        assert_or_throw(
            df.metadata.get("serialized", False),
            FugueInvalidOperation("df is not serialized (run zip first)"),
        )
        meta = dict(df.metadata)
        keys = list(meta.get("keys", []))
        spec = partition_spec or EMPTY_PARTITION_SPEC
        if len(keys) > 0:
            spec = PartitionSpec(spec, by=keys)
        out_schema = (
            output_schema if isinstance(output_schema, Schema) else Schema(output_schema)
        )
        comap_runner = _Comap(meta, map_func, on_init, out_schema)
        return self.map_engine.map_dataframe(
            df, comap_runner.run, out_schema, spec, on_init=comap_runner.on_init
        )

    # ---- yields (reference :941, :1113) -----------------------------------
    def convert_yield_dataframe(self, df: DataFrame, as_local: bool) -> DataFrame:
        return df.as_local() if as_local else df

    def load_yielded(self, df: Yielded) -> DataFrame:
        if isinstance(df, YieldedDataFrame):
            return self.to_df(df.result)
        if isinstance(df, PhysicalYielded):
            if df.storage_type == "file":
                return self.load_df(df.name)
            return self.sql_engine.load_table(df.name)
        raise FugueBug(f"unknown yield type {type(df)}")

    def __uuid__(self) -> str:
        return to_uuid(str(type(self)), id(self))


def _is_plain_col(c: ColumnExpr, name: str) -> bool:
    from ..column.expressions import _NamedColumnExpr

    return isinstance(c, _NamedColumnExpr) and c.name == name


class _PartitionSerializer:
    """Serialize each logical partition into one blob row (arrow IPC)."""

    def __init__(
        self,
        df_index: int,
        df_count: int,
        keys: List[str],
        temp_path: Optional[str],
        to_file_threshold: int,
    ):
        self.df_index = df_index
        self.df_count = df_count
        self.keys = keys
        self.temp_path = temp_path
        self.to_file_threshold = to_file_threshold

    def run(self, cursor: PartitionCursor, df: LocalDataFrame) -> LocalDataFrame:
        data = df.as_local_bounded()
        file_path = (
            get_temp_df_path(self.temp_path) if self.temp_path is not None else None
        )
        blob = serialize_df(data, self.to_file_threshold, file_path)
        row: List[Any] = []
        if len(self.keys) > 0:
            row.extend(cursor.key_value_array)
        blobs: List[Any] = [None] * self.df_count
        blobs[self.df_index] = blob
        row.extend(blobs)
        key_schema = (
            cursor.row_schema.extract(self.keys) if len(self.keys) > 0 else Schema()
        )
        blob_fields = ",".join(
            f"{_FUGUE_BLOB_PREFIX}{i}:binary" for i in range(self.df_count)
        )
        out_schema = (
            Schema(str(key_schema) + "," + blob_fields)
            if len(self.keys) > 0
            else Schema(blob_fields)
        )
        return ArrayDataFrame([row], out_schema)


class _Comap:
    """Reassemble per-key DataFrames from blob rows and run the cotransform
    (reference ``:1293-1353``)."""

    def __init__(
        self,
        meta: Dict[str, Any],
        func: Callable,
        on_init: Optional[Callable],
        output_schema: Schema,
    ):
        self.schemas = [Schema(s) for s in meta["schemas"]]
        self.output_schema = output_schema
        self.named = meta.get("serialized_has_name", False)
        self.names = meta.get("names", [])
        self.how = meta.get("how", "inner")
        self.keys = meta.get("keys", [])
        self.func = func
        self._on_init = on_init

    def on_init(self, partition_no: int, df: DataFrame) -> None:
        if self._on_init is None:
            return
        empty = DataFrames(
            {self._name(i): ArrayDataFrame([], s) for i, s in enumerate(self.schemas)}
        )
        self._on_init(partition_no, empty)

    def _name(self, i: int) -> str:
        if self.named and i < len(self.names):
            return self.names[i]
        return f"_{i}"

    def run(self, cursor: PartitionCursor, df: LocalDataFrame) -> LocalDataFrame:
        import pyarrow as pa

        data = df.as_local_bounded().as_array()
        schema = df.schema
        blob_idx = [
            schema.index_of_key(f"{_FUGUE_BLOB_PREFIX}{i}")
            for i in range(len(self.schemas))
        ]
        frames: List[Optional[LocalBoundedDataFrame]] = []
        for i, s in enumerate(self.schemas):
            tables = []
            for row in data:
                blob = row[blob_idx[i]]
                if blob is not None:
                    tables.append(deserialize_df(blob).native)
            if len(tables) == 0:
                frames.append(None)
            else:
                from ..dataframe import ArrowDataFrame

                frames.append(ArrowDataFrame(pa.concat_tables(tables)))
        # zip-join semantics on missing sides
        if self.how == "inner" and any(f is None for f in frames):
            return ArrayDataFrame([], self.output_schema)
        if self.how == "left_outer" and frames[0] is None:
            return ArrayDataFrame([], self.output_schema)
        if self.how == "right_outer" and frames[-1] is None:
            return ArrayDataFrame([], self.output_schema)
        dfs = DataFrames(
            {
                self._name(i): (
                    f if f is not None else ArrayDataFrame([], self.schemas[i])
                )
                for i, f in enumerate(frames)
            }
        )
        return self.func(cursor, dfs)

