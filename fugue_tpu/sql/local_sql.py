"""The in-tree SQL engine facet.

Replaces the reference's qpd (pandas SQL) and DuckDB SQL engines
(`fugue/execution/native_execution_engine.py:42-66`,
`fugue_duckdb/execution_engine.py:36`) — neither dependency exists here.
SQL parses to a logical plan and executes through the PARENT execution
engine's verbs, so the same SQL distributes on the TPU engine.

Tables (for deterministic-checkpoint ``storage_type="table"`` and
``yield_table_as``) are parquet files in a managed directory — the host-side
"warehouse" equivalent.
"""

import os
from typing import Any, Dict, Optional

from ..collections.sql import StructuredRawSQL
from ..dataframe import DataFrame, DataFrames
from ..execution.execution_engine import SQLEngine
from ..obs import get_tracer
from .executor import SQLExecutor
from .parser import SQLParser


class LocalSQLEngine(SQLEngine):
    """Dialect: spark-ish subset, parsed in-tree."""

    @property
    def is_distributed(self) -> bool:
        return self.execution_engine.is_distributed

    @property
    def dialect(self) -> Optional[str]:
        return "spark"

    def select(self, dfs: DataFrames, statement: StructuredRawSQL) -> DataFrame:
        sql = statement.construct(dialect=self.dialect, log=self.log)
        with get_tracer().span("sql.parse", annotate=True, chars=len(sql)):
            plan = SQLParser(sql).parse_full()
        return SQLExecutor(self.execution_engine, dict(dfs)).run(plan)

    # -- table storage ------------------------------------------------------
    def _table_dir(self) -> str:
        from ..constants import FUGUE_CONF_WORKFLOW_CHECKPOINT_PATH

        base = self.conf.get(FUGUE_CONF_WORKFLOW_CHECKPOINT_PATH, "")
        if base == "":
            import tempfile

            base = os.path.join(tempfile.gettempdir(), "fugue_tpu_tables")
        path = os.path.join(base, "_tables")
        os.makedirs(path, exist_ok=True)
        return path

    def _table_path(self, table: str) -> str:
        return os.path.join(self._table_dir(), table + ".parquet")

    def table_exists(self, table: str) -> bool:
        return os.path.exists(self._table_path(table))

    def save_table(
        self,
        df: DataFrame,
        table: str,
        mode: str = "overwrite",
        partition_spec: Any = None,
        **kwargs: Any,
    ) -> None:
        self.execution_engine.save_df(
            df, self._table_path(table), format_hint="parquet", mode=mode, **kwargs
        )

    def load_table(self, table: str, **kwargs: Any) -> DataFrame:
        return self.execution_engine.load_df(
            self._table_path(table), format_hint="parquet"
        )
