"""Dataset: the lazy, streaming, distributed data API.

Counterpart of python/ray/data/dataset.py (Dataset :139) and read_api.py.
A Dataset wraps a LogicalPlan; transforms append logical ops; consumption
lowers to physical operators and drives the StreamingExecutor
(execution.py).  `streaming_split` (dataset.py:1236 in the reference)
serves N trainer workers from one coordinator actor.
"""

from __future__ import annotations

import builtins
import itertools
from typing import (
    Any,
    Callable,
    Dict,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import numpy as np
import pyarrow as pa

import ray_tpu
from ray_tpu.data import logical as L
from ray_tpu.data.block import (
    Block,
    BlockAccessor,
    block_to_batch,
    concat_blocks,
)
from ray_tpu.data.datasource import (
    BlocksDatasource,
    CSVDatasource,
    Datasource,
    ItemsDatasource,
    JSONDatasource,
    NumpyDatasource,
    ParquetDatasource,
    RangeDatasource,
    write_block_csv,
    write_block_json,
    write_block_parquet,
)
from ray_tpu.data.execution import RefBundle, StreamingExecutor
from ray_tpu.data.iterator import DataIterator
from ray_tpu.data.planner import execute_plan


class Dataset:
    def __init__(self, terminal: L.LogicalOp):
        self._terminal = terminal
        self._materialized: Optional[List[RefBundle]] = None

    # ------------------------------------------------------------------
    # Transforms (lazy)
    # ------------------------------------------------------------------
    def _append(self, op: L.LogicalOp) -> "Dataset":
        op.inputs = [self._terminal]
        return Dataset(op)

    def map_batches(self, fn=None, *, batch_size: Optional[int] = None,
                    batch_format: str = "numpy",
                    fn_constructor: Optional[Callable[[], Any]] = None,
                    num_cpus: float = 1.0,
                    concurrency: Optional[int] = None,
                    compute: Optional[str] = None) -> "Dataset":
        """compute="actors" runs this op on a pool of long-lived actors
        (callable class constructed once per actor, state reused across
        tasks — the reference's ActorPoolStrategy); default is stateless
        pool tasks."""
        if fn is None and fn_constructor is None:
            raise ValueError("map_batches requires fn or fn_constructor")
        if compute not in (None, "tasks", "actors"):
            raise ValueError(f"compute must be 'tasks' or 'actors', "
                             f"got {compute!r}")
        return self._append(L.MapBatches(
            fn=fn, batch_size=batch_size, batch_format=batch_format,
            fn_constructor=fn_constructor, num_cpus=num_cpus,
            concurrency=concurrency, compute=compute))

    def map(self, fn: Callable[[Dict], Dict]) -> "Dataset":
        return self._append(L.MapRows(fn=fn))

    def flat_map(self, fn: Callable[[Dict], Sequence[Dict]]) -> "Dataset":
        return self._append(L.FlatMapRows(fn=fn))

    def filter(self, fn: Callable[[Dict], bool]) -> "Dataset":
        return self._append(L.FilterRows(fn=fn))

    def add_column(self, name: str, fn: Callable[[Dict], Any]) -> "Dataset":
        def _add(batch: Dict[str, np.ndarray]):
            n = len(next(iter(batch.values()))) if batch else 0
            rows = ({k: v[i] for k, v in batch.items()}
                    for i in np.arange(n))
            batch = dict(batch)
            batch[name] = np.asarray([fn(r) for r in rows])
            return batch

        return self.map_batches(_add)

    def select_columns(self, cols: Sequence[str]) -> "Dataset":
        return self.map_batches(
            lambda t: t.select(list(cols)), batch_format="pyarrow")

    def drop_columns(self, cols: Sequence[str]) -> "Dataset":
        drop = set(cols)

        def _drop(t: pa.Table):
            return t.select([n for n in t.schema.names if n not in drop])

        return self.map_batches(_drop, batch_format="pyarrow")

    def rename_columns(self, mapping: Dict[str, str]) -> "Dataset":
        return self.map_batches(
            lambda t: BlockAccessor(t).rename_columns(mapping),
            batch_format="pyarrow")

    def limit(self, n: int) -> "Dataset":
        return self._append(L.Limit(limit=n))

    def repartition(self, num_blocks: int) -> "Dataset":
        return self._append(L.Repartition(num_blocks=num_blocks))

    def random_shuffle(self, *, seed: Optional[int] = None) -> "Dataset":
        return self._append(L.RandomShuffle(seed=seed))

    def sort(self, key: str, descending: bool = False) -> "Dataset":
        return self._append(L.Sort(key=key, descending=descending))

    def union(self, *others: "Dataset") -> "Dataset":
        op = L.Union()
        op.inputs = [self._terminal] + [o._terminal for o in others]
        return Dataset(op)

    def zip(self, other: "Dataset") -> "Dataset":
        op = L.Zip()
        op.inputs = [self._terminal, other._terminal]
        return Dataset(op)

    # -- global aggregates (reference Dataset.sum/min/max/mean/std) ----
    def _global_agg(self, kind: str, on: str):
        rows = GroupedData(self, None)._agg(kind, on).take_all()
        return rows[0][f"{kind}({on})"] if rows else None

    def sum(self, on: str):
        return self._global_agg("sum", on)

    def min(self, on: str):
        return self._global_agg("min", on)

    def max(self, on: str):
        return self._global_agg("max", on)

    def mean(self, on: str):
        return self._global_agg("mean", on)

    def std(self, on: str):
        return self._global_agg("std", on)

    def groupby(self, key: Optional[str]) -> "GroupedData":
        return GroupedData(self, key)

    def random_sample(self, fraction: float,
                      seed: Optional[int] = None) -> "Dataset":
        def _sample(batch: pa.Table, _seed=[seed]):
            rng = np.random.default_rng(_seed[0])
            if _seed[0] is not None:
                _seed[0] += 1
            mask = rng.random(batch.num_rows) < fraction
            return BlockAccessor(batch).take(np.nonzero(mask)[0].tolist())

        return self.map_batches(_sample, batch_format="pyarrow")

    # ------------------------------------------------------------------
    # Execution / consumption
    # ------------------------------------------------------------------
    def _plan(self) -> L.LogicalPlan:
        if self._materialized is not None:
            read = L.Read(datasource=_MaterializedSource(self._materialized))
            return L.LogicalPlan(read)
        return L.LogicalPlan(self._terminal)

    def _execute(self) -> StreamingExecutor:
        return execute_plan(self._plan())

    def iter_internal_blocks(self) -> Iterator[Block]:
        ex = self._execute()
        for bundle in ex.output_bundles():
            for block in ray_tpu.get(bundle.blocks_ref):
                yield block

    def iterator(self) -> DataIterator:
        return DataIterator(self.iter_internal_blocks)

    def iter_batches(self, **kw) -> Iterator[Any]:
        return self.iterator().iter_batches(**kw)

    def iter_torch_batches(self, **kw) -> Iterator[Any]:
        return self.iterator().iter_torch_batches(**kw)

    def iter_rows(self) -> Iterator[Dict[str, Any]]:
        return self.iterator().iter_rows()

    def iter_device_batches(self, **kw) -> Iterator[Any]:
        return self.iterator().iter_device_batches(**kw)

    def take(self, n: int = 20) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        for row in self.limit(n).iter_rows():
            out.append(row)
            if len(out) >= n:
                break
        return out

    def take_all(self) -> List[Dict[str, Any]]:
        return list(self.iter_rows())

    def take_batch(self, n: int = 20, batch_format: str = "numpy"):
        block = concat_blocks(
            list(self.limit(n).iter_internal_blocks()))
        return block_to_batch(block, batch_format)

    def count(self) -> int:
        if self._materialized is not None:
            return sum(b.num_rows for b in self._materialized)
        # Fast path for pure reads with known cardinality.
        if isinstance(self._terminal, L.Read):
            n = self._terminal.datasource.num_rows()
            if n is not None:
                return n
        ex = self._execute()
        return sum(b.num_rows for b in ex.output_bundles())

    def schema(self):
        """First block's schema: a pyarrow.Schema under the default
        block format, or a names-only shim under
        DataContext.block_format="pandas" (both expose ``.names``)."""
        for block in self.limit(1).iter_internal_blocks():
            return block.schema
        return None

    def columns(self) -> List[str]:
        schema = self.schema()
        return list(schema.names) if schema is not None else []

    def materialize(self) -> "Dataset":
        """Execute now; the result holds block refs and re-reads are free
        (reference Dataset.materialize → MaterializedDataset)."""
        ex = self._execute()
        bundles = list(ex.output_bundles())
        ds = Dataset(self._terminal)
        ds._materialized = bundles
        return ds

    def stats(self) -> str:
        if self._materialized is not None:
            rows = sum(b.num_rows for b in self._materialized)
            return f"Materialized: {len(self._materialized)} bundles, {rows} rows"
        return "Lazy plan: " + self._plan().describe()

    def num_blocks(self) -> Optional[int]:
        if self._materialized is not None:
            return len(self._materialized)
        return None

    # ------------------------------------------------------------------
    # Splitting
    # ------------------------------------------------------------------
    def split(self, n: int, *, equal: bool = False) -> List["Dataset"]:
        """Materializing split into n datasets (reference Dataset.split);
        equal=True truncates the remainder so every child has exactly
        total // n rows."""
        combined = self._combined_block()
        total = combined.num_rows
        if equal:
            per = total // n
            combined = BlockAccessor(combined).slice(0, per * n)
            total = per * n
        else:
            per = -(-total // n)
        bounds = [min(i * per, total) for i in builtins.range(1, n)]
        return self._split_combined(combined, bounds)

    def _split_combined(self, combined, bounds: List[int]
                        ) -> List["Dataset"]:
        """Children sliced from one combined block at `bounds` (sorted
        row indices); len(bounds)+1 datasets."""
        total = combined.num_rows
        acc = BlockAccessor(combined)
        out = []
        for start, end in builtins.zip([0, *bounds], [*bounds, total]):
            start, end = min(start, total), min(end, total)
            piece = acc.slice(start, end)
            child = Dataset(self._terminal)
            child._materialized = [RefBundle.from_blocks([piece])] \
                if piece.num_rows else []
            out.append(child)
        return out

    def _combined_block(self):
        mat = self if self._materialized is not None else self.materialize()
        # Bundles arrive in the order their tasks finished; rows are cut
        # in source order (the key zip orders by too).
        blocks = [b for bundle in sorted(mat._materialized or [],
                                         key=lambda bundle: bundle.seq)
                  for b in ray_tpu.get(bundle.blocks_ref)]
        return concat_blocks(blocks) if blocks else pa.table({})

    def split_at_indices(self, indices: Sequence[int]) -> List["Dataset"]:
        """Split at sorted row indices → len(indices)+1 datasets
        (reference Dataset.split_at_indices)."""
        bounds = list(indices)
        if bounds != sorted(bounds) or any(i < 0 for i in bounds):
            raise ValueError("indices must be sorted and non-negative")
        return self._split_combined(self._combined_block(), bounds)

    def split_proportionately(self, proportions: Sequence[float]
                              ) -> List["Dataset"]:
        """Split by fractions (must sum to < 1; the remainder forms the
        final dataset — reference Dataset.split_proportionately)."""
        if any(p <= 0 for p in proportions) or sum(proportions) >= 1:
            raise ValueError(
                "proportions must be positive and sum to less than 1")
        combined = self._combined_block()
        total = combined.num_rows
        bounds, acc = [], 0.0
        for p in proportions:
            acc += p
            bounds.append(int(total * acc))
        return self._split_combined(combined, bounds)

    def train_test_split(self, test_size: float, *,
                         shuffle: bool = False,
                         seed: Optional[int] = None
                         ) -> Tuple["Dataset", "Dataset"]:
        """(train, test) datasets (reference Dataset.train_test_split);
        test_size is a fraction in (0, 1) or an absolute row count."""
        ds = self.random_shuffle(seed=seed) if shuffle else self
        combined = ds._combined_block()
        total = combined.num_rows
        if isinstance(test_size, float):
            if not 0 < test_size < 1:
                raise ValueError("test_size fraction must be in (0, 1)")
            # Reference parity: split_proportionately([1 - test_size])
            # puts int(total * (1 - test_size)) rows in train.
            n_train = int(total * (1 - test_size))
        else:
            n_test = int(test_size)
            if not 0 <= n_test <= total:
                raise ValueError(f"test_size {n_test} out of range")
            n_train = total - n_test
        train, test = ds._split_combined(combined, [n_train])
        return train, test

    def unique(self, column: str) -> List[Any]:
        """Distinct values of one column, in first-seen order with the
        ORIGINAL values (lists stay lists; reference Dataset.unique)."""
        from ray_tpu.data.block import block_to_arrow

        _NULL_SENTINEL = ("__ray_tpu_null__",)

        def hashable(v):
            if isinstance(v, list):
                return tuple(hashable(x) for x in v)
            if isinstance(v, dict):
                return tuple(sorted(
                    (k, hashable(x)) for k, x in v.items()))
            if v is None:
                return _NULL_SENTINEL
            if isinstance(v, float) and v != v:
                # NaN != NaN, so raw-value keys would keep every NaN
                # row as "unique"; collapse all nulls to one sentinel.
                return _NULL_SENTINEL
            return v

        seen: Dict[Any, Any] = {}
        for block in self.iter_internal_blocks():
            col = block_to_arrow(block)[column]
            for v in col.to_pylist():
                seen.setdefault(hashable(v), v)
        return list(seen.values())

    def randomize_block_order(self, *, seed: Optional[int] = None
                              ) -> "Dataset":
        """Shuffle BLOCK order without touching rows — the cheap
        epoch-level shuffle (reference Dataset.randomize_block_order)."""
        mat = self if self._materialized is not None else self.materialize()
        bundles = list(mat._materialized or [])
        np.random.default_rng(seed).shuffle(bundles)
        ds = Dataset(self._terminal)
        ds._materialized = bundles
        return ds

    def size_bytes(self) -> int:
        """In-memory byte estimate (reference Dataset.size_bytes); both
        block types expose .nbytes directly — no Arrow conversion."""
        return sum(b.nbytes for b in self.iter_internal_blocks())

    def show(self, limit: int = 20) -> None:
        """Print up to `limit` rows (reference Dataset.show)."""
        for row in self.take(limit):
            print(row)

    def streaming_split(self, n: int, *, equal: bool = False,
                        locality_hints=None) -> List[DataIterator]:
        """N iterators fed concurrently by one executing pipeline
        (reference dataset.py:1236 + stream_split_iterator.py).  Used by
        the trainer to feed per-worker shards."""
        coordinator = _SplitCoordinator.options(
            max_concurrency=max(2, 2 * n)).remote(
                _PlanCapsule(self._terminal, self._materialized), n, equal)

        def make_source(idx: int) -> Callable[[], Iterator[Block]]:
            def source() -> Iterator[Block]:
                epoch = ray_tpu.get(coordinator.start_epoch.remote(idx))
                while True:
                    blocks = ray_tpu.get(
                        coordinator.get_next.remote(idx, epoch))
                    if blocks is None:
                        return
                    yield from blocks

            return source

        return [DataIterator(make_source(i)) for i in builtins.range(n)]

    # ------------------------------------------------------------------
    # Writes
    # ------------------------------------------------------------------
    def _write(self, write_fn, path: str) -> List[str]:
        op = L.Write(write_fn=write_fn, path=path)
        op.inputs = [self._terminal]
        ds = Dataset(op)
        return [r["path"] for r in ds.take_all()]

    def write_parquet(self, path: str) -> List[str]:
        return self._write(write_block_parquet, path)

    def write_csv(self, path: str) -> List[str]:
        return self._write(write_block_csv, path)

    def write_json(self, path: str) -> List[str]:
        return self._write(write_block_json, path)

    def write_tfrecords(self, path: str) -> List[str]:
        """tf.train.Example files readable by TensorFlow (and
        read_tfrecords); no tensorflow needed (data/tfrecords.py)."""
        from ray_tpu.data.datasource import write_block_tfrecords

        return self._write(write_block_tfrecords, path)

    def write_numpy(self, path: str, *, column: str = "data"
                    ) -> List[str]:
        """One .npy per block from `column` (reference
        Dataset.write_numpy / numpy_datasink.py)."""
        import functools

        from ray_tpu.data.datasource import write_block_numpy

        return self._write(
            functools.partial(write_block_numpy, column=column), path)

    def write_images(self, path: str, *, column: str = "image",
                     file_format: str = "png") -> List[str]:
        """One image file per row (reference Dataset.write_images)."""
        import functools

        from ray_tpu.data.datasource import write_block_images

        return self._write(
            functools.partial(write_block_images, column=column,
                              file_format=file_format), path)

    def write_sql(self, sql: str, connection_factory) -> List[str]:
        """executemany `sql` (an INSERT with placeholders) over every
        block; the factory opens connections inside the write tasks
        (reference Dataset.write_sql / sql_datasink.py)."""
        import functools

        from ray_tpu.data.datasource import write_block_sql

        return self._write(
            functools.partial(write_block_sql, sql=sql,
                              connection_factory=connection_factory),
            "")

    def write_mongo(self, uri: str, database: str, collection: str, *,
                    _module=None) -> List[str]:
        """insert_many every block's rows (reference
        Dataset.write_mongo; gated on pymongo)."""
        import functools

        from ray_tpu.data.datasource import write_block_mongo

        return self._write(
            functools.partial(write_block_mongo, uri=uri,
                              database=database, collection=collection,
                              _module=_module), "")

    def write_bigquery(self, project_id: str, dataset: str, *,
                       _module=None) -> List[str]:
        """Load every block into `project.dataset` (reference
        Dataset.write_bigquery; gated on google-cloud-bigquery)."""
        import functools

        from ray_tpu.data.datasource import write_block_bigquery

        return self._write(
            functools.partial(write_block_bigquery,
                              project_id=project_id, dataset=dataset,
                              _module=_module), "")

    def write_avro(self, path: str) -> List[str]:
        """Avro Object Container Files, deflate codec, schema inferred
        per block; no avro package needed (data/avro.py)."""
        from ray_tpu.data.datasource import write_block_avro

        return self._write(write_block_avro, path)

    def write_webdataset(self, path: str) -> List[str]:
        """One WebDataset tar shard per block; column names become the
        member suffixes (reference webdataset_datasink.py)."""
        from ray_tpu.data.datasource import write_block_webdataset

        return self._write(write_block_webdataset, path)

    def to_pandas(self):
        return concat_blocks(
            list(self.iter_internal_blocks())).to_pandas()

    def to_arrow_refs(self) -> List[Any]:
        """One ObjectRef per block holding its arrow Table (reference
        Dataset.to_arrow_refs); pairs with from_arrow_refs."""
        from ray_tpu.data.block import block_to_arrow

        return [ray_tpu.put(block_to_arrow(b))
                for b in self.iter_internal_blocks()]

    def to_pandas_refs(self) -> List[Any]:
        """One ObjectRef per block as a pandas DataFrame (reference
        Dataset.to_pandas_refs)."""
        from ray_tpu.data.block import block_to_arrow

        return [ray_tpu.put(block_to_arrow(b).to_pandas())
                for b in self.iter_internal_blocks()]

    def to_numpy_refs(self, *, column: Optional[str] = None
                      ) -> List[Any]:
        """One ObjectRef per block: a single column's ndarray, or a
        dict of column ndarrays (reference Dataset.to_numpy_refs)."""
        from ray_tpu.data.block import BlockAccessor

        out = []
        for b in self.iter_internal_blocks():
            batch = BlockAccessor(b).to_batch()
            out.append(ray_tpu.put(
                batch[column] if column is not None else batch))
        return out

    def to_dask(self, *, _module=None):
        """dask.dataframe over one partition per block (reference
        Dataset.to_dask; gated like data/external.py)."""
        from ray_tpu.data.block import block_to_arrow
        from ray_tpu.data.external import _import

        dd = _import("dask.dataframe", "dask[dataframe]",
                     "use to_pandas / iter_batches", _module)
        dfs = [block_to_arrow(b).to_pandas()
               for b in self.iter_internal_blocks()]
        if not dfs:
            import pandas as pd

            return dd.from_pandas(pd.DataFrame(), npartitions=1)
        return dd.concat([dd.from_pandas(df, npartitions=1)
                          for df in dfs])

    def to_modin(self, *, _module=None):
        """modin DataFrame (reference Dataset.to_modin; gated)."""
        from ray_tpu.data.external import _import

        mpd = _import("modin.pandas", "modin",
                      "use to_pandas", _module)
        return mpd.DataFrame(self.to_pandas())

    def to_spark(self, spark_session):
        """pyspark DataFrame via the session's createDataFrame
        (reference Dataset.to_spark; duck-typed on the session)."""
        if not hasattr(spark_session, "createDataFrame"):
            raise TypeError(
                "to_spark expects a SparkSession (.createDataFrame)")
        return spark_session.createDataFrame(self.to_pandas())

    def to_tf(self, *, _module=None):
        """tf.data.Dataset over the rows via from_tensor_slices
        (reference Dataset.to_tf; gated on tensorflow)."""
        from ray_tpu.data.block import BlockAccessor
        from ray_tpu.data.external import _import

        tf = _import("tensorflow", "tensorflow",
                     "use iter_batches / iter_torch_batches", _module)
        blocks = list(self.iter_internal_blocks())
        combined = concat_blocks(blocks) if blocks else pa.table({})
        batch = BlockAccessor(combined).to_batch()
        return tf.data.Dataset.from_tensor_slices(batch)

    def to_arrow(self) -> pa.Table:
        from ray_tpu.data.block import block_to_arrow

        return block_to_arrow(
            concat_blocks(list(self.iter_internal_blocks())))

    def __repr__(self):
        return f"Dataset(plan={self._plan().describe()})"


class _MaterializedSource(Datasource):
    """Re-serves already-executed bundles (zero-cost re-read)."""

    def __init__(self, bundles: List[RefBundle]):
        self._bundles = bundles

    def num_rows(self) -> Optional[int]:
        return sum(b.num_rows for b in self._bundles)

    def get_read_tasks(self, parallelism: int):
        from ray_tpu.data.block import BlockMetadata
        from ray_tpu.data.datasource import ReadTask

        tasks = []
        for bundle in self._bundles:
            ref = bundle.blocks_ref

            def fn(ref=ref):
                yield from ray_tpu.get(ref)

            tasks.append(ReadTask(fn, BlockMetadata(
                num_rows=bundle.num_rows, size_bytes=bundle.size_bytes)))
        return tasks


class _PlanCapsule:
    """Pickles a logical plan (or materialized bundles) into the coordinator
    actor."""

    def __init__(self, terminal: L.LogicalOp,
                 materialized: Optional[List[RefBundle]]):
        self.terminal = terminal
        self.materialized = materialized

    def to_dataset(self) -> Dataset:
        ds = Dataset(self.terminal)
        ds._materialized = self.materialized
        return ds


@ray_tpu.remote
class _SplitCoordinator:
    """Runs the streaming executor once per epoch; consumers pull blocks
    for their split index (reference stream_split_iterator.py).

    Epoch protocol: each consumer's k-th start_epoch call requests epoch
    k-1; the pump for an epoch starts only once EVERY consumer has
    requested it (a barrier — prevents a fast consumer from observing a
    stale epoch and silently skipping it).  equal=True stages the whole
    epoch, truncates every split to the minimum row count, then releases —
    consumers can never overconsume surplus rows mid-stream."""

    def __init__(self, capsule: _PlanCapsule, n: int, equal: bool):
        import collections
        import threading

        self._capsule = capsule
        self._n = n
        self._equal = equal
        self._lock = threading.Lock()
        self._epoch = -1
        self._requests = [-1] * n  # highest epoch each consumer asked for
        self._queues: List = [collections.deque()
                              for _ in builtins.range(n)]
        self._done = False
        self._thread = None
        self._cond = threading.Condition(self._lock)

    def start_epoch(self, idx: int) -> int:
        """Consumer idx requests its next epoch; blocks until the epoch is
        live (all consumers arrived), then returns its id."""
        import threading

        with self._cond:
            self._requests[idx] += 1
            want = self._requests[idx]
            while self._epoch < want:
                ready = (min(self._requests) >= want
                         and (self._thread is None or self._done)
                         and not any(self._queues))
                if ready:
                    self._advance(want)
                    break
                self._cond.wait(timeout=1.0)
            return want

    def _advance(self, epoch: int):
        """Lock held: reset state and launch the pump for ``epoch``."""
        import collections
        import threading

        self._epoch = epoch
        self._done = False
        self._queues = [collections.deque()
                        for _ in builtins.range(self._n)]
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self):
        import numpy as np

        ds = self._capsule.to_dataset()
        ex = ds._execute()
        rows = [0] * self._n
        staged: List[List] = [[] for _ in builtins.range(self._n)]
        try:
            for bundle in ex.output_bundles():
                blocks = ray_tpu.get(bundle.blocks_ref)
                tgt = int(np.argmin(rows))
                rows[tgt] += bundle.num_rows
                if self._equal and self._n > 1:
                    staged[tgt].append(blocks)  # hold back until equalized
                else:
                    with self._cond:
                        self._queues[tgt].append(blocks)
                        self._cond.notify_all()
            if self._equal and self._n > 1:
                self._release_equalized(staged, rows)
        finally:
            with self._cond:
                self._done = True
                self._cond.notify_all()

    def _release_equalized(self, staged: List[List], rows: List[int]):
        target = min(rows)
        for i in builtins.range(self._n):
            surplus = rows[i] - target
            out = list(staged[i])
            while surplus > 0 and out:
                blocks = out.pop()
                have = sum(b.num_rows for b in blocks)
                if have <= surplus:
                    surplus -= have
                    continue
                combined = concat_blocks(blocks)
                keep = combined.num_rows - surplus
                out.append([BlockAccessor(combined).slice(0, keep)])
                surplus = 0
            with self._cond:
                self._queues[i].extend(out)
                self._cond.notify_all()

    def get_next(self, idx: int, epoch: int):
        with self._cond:
            while True:
                if epoch != self._epoch:
                    return None  # stale consumer (pre-barrier epochs only)
                if self._queues[idx]:
                    return self._queues[idx].popleft()
                if self._done:
                    return None
                self._cond.wait(timeout=1.0)


class GroupedData:
    """Counterpart of python/ray/data/grouped_data.py."""

    _KINDS = ("sum", "min", "max", "mean", "count", "std")

    def __init__(self, ds: Dataset, key: Optional[str]):
        self._ds = ds
        self._key = key

    def _agg(self, kind: str, on: Union[str, Sequence[str]]) -> Dataset:
        cols = [on] if isinstance(on, str) else list(on)
        aggs = [(kind, c, f"{kind}({c})") for c in cols]
        op = L.GroupByAggregate(key=self._key, aggs=tuple(aggs))
        op.inputs = [self._ds._terminal]
        return Dataset(op)

    def sum(self, on) -> Dataset:
        return self._agg("sum", on)

    def min(self, on) -> Dataset:
        return self._agg("min", on)

    def max(self, on) -> Dataset:
        return self._agg("max", on)

    def mean(self, on) -> Dataset:
        return self._agg("mean", on)

    def std(self, on) -> Dataset:
        return self._agg("std", on)

    def count(self) -> Dataset:
        key = self._key
        if key is None:
            raise ValueError("count() requires a groupby key")
        op = L.GroupByAggregate(
            key=key, aggs=(("count", key, "count()"),))
        op.inputs = [self._ds._terminal]
        return Dataset(op)

    def aggregate(self, *specs: Sequence[Any]) -> Dataset:
        """specs: (kind, on_column[, out_name]) tuples."""
        aggs = []
        for spec in specs:
            kind, on = spec[0], spec[1]
            out_name = spec[2] if len(spec) > 2 else f"{kind}({on})"
            if kind not in self._KINDS:
                raise ValueError(f"unknown aggregate {kind!r}")
            aggs.append((kind, on, out_name))
        op = L.GroupByAggregate(key=self._key, aggs=tuple(aggs))
        op.inputs = [self._ds._terminal]
        return Dataset(op)

    def map_groups(self, fn, *, batch_format: str = "pandas") -> Dataset:
        """Apply `fn` once per key-group (reference
        grouped_data.py map_groups): fn receives the whole group as a
        pandas DataFrame ("pandas") or dict-of-ndarrays ("numpy") and
        returns a batch, a DataFrame, a list of rows, or None."""
        if self._key is None:
            raise ValueError("map_groups() requires a groupby key")
        if batch_format not in ("pandas", "numpy"):
            raise ValueError("batch_format must be 'pandas' or 'numpy'")
        op = L.GroupByMapGroups(key=self._key, fn=fn,
                                batch_format=batch_format)
        op.inputs = [self._ds._terminal]
        return Dataset(op)


# ---------------------------------------------------------------------------
# Read API (counterpart of python/ray/data/read_api.py)
# ---------------------------------------------------------------------------


def read_datasource(ds: Datasource, *, parallelism: int = -1) -> Dataset:
    return Dataset(L.Read(datasource=ds, parallelism=parallelism))


def range(n: int, *, parallelism: int = -1) -> Dataset:  # noqa: A001
    return read_datasource(RangeDatasource(n), parallelism=parallelism)


def range_tensor(n: int, *, shape=(1,), parallelism: int = -1) -> Dataset:
    return read_datasource(
        RangeDatasource(n, tensor_shape=shape), parallelism=parallelism)


def from_items(items: Sequence[Any], *, parallelism: int = -1) -> Dataset:
    return read_datasource(ItemsDatasource(items), parallelism=parallelism)


def from_arrow(tables: Union[pa.Table, Sequence[pa.Table]]) -> Dataset:
    if isinstance(tables, pa.Table):
        tables = [tables]
    return read_datasource(BlocksDatasource(list(tables)))


def from_pandas(dfs) -> Dataset:
    import pandas as pd

    if isinstance(dfs, pd.DataFrame):
        dfs = [dfs]
    return from_arrow(
        [pa.Table.from_pandas(df, preserve_index=False) for df in dfs])


def from_numpy(arrays, column: str = "data") -> Dataset:
    from ray_tpu.data.block import batch_to_block

    if isinstance(arrays, np.ndarray):
        arrays = [arrays]
    return from_arrow([batch_to_block({column: a}) for a in arrays])


def read_parquet(paths, *, columns=None, parallelism: int = -1) -> Dataset:
    return read_datasource(
        ParquetDatasource(paths, columns=columns), parallelism=parallelism)


def read_csv(paths, *, parallelism: int = -1) -> Dataset:
    return read_datasource(CSVDatasource(paths), parallelism=parallelism)


def read_json(paths, *, parallelism: int = -1) -> Dataset:
    return read_datasource(JSONDatasource(paths), parallelism=parallelism)


def read_numpy(paths, *, column: str = "data",
               parallelism: int = -1) -> Dataset:
    return read_datasource(
        NumpyDatasource(paths, column=column), parallelism=parallelism)


def read_text(paths, *, encoding: str = "utf-8",
              drop_empty_lines: bool = True,
              parallelism: int = -1) -> Dataset:
    """One row per line, column "text" (reference read_api.read_text)."""
    from ray_tpu.data.datasource import TextDatasource

    return read_datasource(
        TextDatasource(paths, encoding=encoding,
                       drop_empty_lines=drop_empty_lines),
        parallelism=parallelism)


def read_tfrecords(paths, *, validate_crc: bool = False,
                   parallelism: int = -1) -> Dataset:
    """One row per tf.train.Example record; columns from feature names
    (reference read_api.read_tfrecords — parsed without tensorflow,
    data/tfrecords.py)."""
    from ray_tpu.data.datasource import TFRecordDatasource

    return read_datasource(
        TFRecordDatasource(paths, validate_crc=validate_crc),
        parallelism=parallelism)


def read_binary_files(paths, *, include_paths: bool = False,
                      parallelism: int = -1) -> Dataset:
    """One row per file, column "bytes" (reference read_binary_files)."""
    from ray_tpu.data.datasource import BinaryDatasource

    return read_datasource(
        BinaryDatasource(paths, include_paths=include_paths),
        parallelism=parallelism)


def read_images(paths, *, size=None, mode: str = None,
                include_paths: bool = False,
                parallelism: int = -1) -> Dataset:
    """One row per image, column "image" as an HWC uint8 array
    (reference read_api.read_images; size=(H, W) resizes for
    fixed-shape device batches)."""
    from ray_tpu.data.datasource import ImageDatasource

    return read_datasource(
        ImageDatasource(paths, size=size, mode=mode,
                        include_paths=include_paths),
        parallelism=parallelism)


def read_sql(sql: str, connection_factory, *,
             parallelism: int = -1) -> Dataset:
    """Rows from a DB-API query; the factory opens the connection inside
    the read task (reference read_api.read_sql)."""
    from ray_tpu.data.datasource import SQLDatasource

    return read_datasource(SQLDatasource(sql, connection_factory),
                           parallelism=parallelism)


def from_torch(torch_dataset, *, column: str = "item",
               parallelism: int = -1) -> Dataset:
    """Map-style torch Dataset → Dataset (reference from_torch); tuple
    items become col_0/col_1/... columns."""
    from ray_tpu.data.datasource import TorchDatasource

    return read_datasource(
        TorchDatasource(torch_dataset, column=column),
        parallelism=parallelism)


def read_parquet_bulk(paths, *, columns=None,
                      parallelism: int = -1) -> Dataset:
    """Many small parquet files without per-file metadata probing on the
    driver (reference read_api.read_parquet_bulk /
    parquet_bulk_datasource.py): identical read path to read_parquet —
    our planner never probes footers driver-side — so this is the same
    datasource with the bulk name kept for API parity."""
    return read_parquet(paths, columns=columns, parallelism=parallelism)


def read_avro(paths, *, parallelism: int = -1) -> Dataset:
    """One row per Avro record, columns from the writer schema's record
    fields; no avro package needed (data/avro.py; reference
    read_api.read_avro)."""
    from ray_tpu.data.datasource import AvroDatasource

    return read_datasource(AvroDatasource(paths), parallelism=parallelism)


def read_webdataset(paths, *, suffixes=None, decoder=True,
                    parallelism: int = -1) -> Dataset:
    """WebDataset tar shards → one row per sample with "__key__" plus a
    column per member suffix (reference read_api.read_webdataset)."""
    from ray_tpu.data.datasource import WebDatasetDatasource

    return read_datasource(
        WebDatasetDatasource(paths, suffixes=suffixes, decoder=decoder),
        parallelism=parallelism)


def from_blocks(blocks) -> Dataset:
    """Dataset over already-built blocks (reference from_blocks)."""
    from ray_tpu.data.datasource import BlocksDatasource

    return read_datasource(BlocksDatasource(list(blocks)))


def from_arrow_refs(refs) -> Dataset:
    """Dataset over ObjectRefs of arrow Tables; refs resolve inside the
    read tasks, not on the driver (reference from_arrow_refs)."""
    from ray_tpu.data.datasource import RefBlocksDatasource

    return read_datasource(RefBlocksDatasource(_listify(refs)))


def from_pandas_refs(refs) -> Dataset:
    """Dataset over ObjectRefs of pandas DataFrames (reference
    from_pandas_refs)."""
    from ray_tpu.data.datasource import RefBlocksDatasource

    return read_datasource(RefBlocksDatasource(_listify(refs)))


def from_numpy_refs(refs, column: str = "data") -> Dataset:
    """Dataset over ObjectRefs of ndarrays (reference from_numpy_refs)."""
    from ray_tpu.data.datasource import RefBlocksDatasource

    return read_datasource(
        RefBlocksDatasource(_listify(refs), column=column))


def _listify(refs):
    return list(refs) if isinstance(refs, (list, tuple)) else [refs]
