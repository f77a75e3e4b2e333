"""Data-source breadth: avro, webdataset, ref-based constructors, and
the gated external connectors (lance/bigquery/mongo/delta-sharing/
databricks/huggingface/dask/spark/modin/mars/tf) against
protocol-faithful stubs (SURVEY.md §2.3 L1; reference read_api.py).
"""

import sys
import types

import numpy as np
import pyarrow as pa
import pytest

import ray_tpu
import ray_tpu.data as rd
from ray_tpu.data import avro


@pytest.fixture(scope="module", autouse=True)
def _rt():
    rt = ray_tpu.init(num_cpus=4)
    yield rt
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Avro
# ---------------------------------------------------------------------------


def test_avro_codec_all_types(tmp_path):
    schema = {
        "type": "record", "name": "r", "fields": [
            {"name": "i", "type": "long"},
            {"name": "f", "type": "double"},
            {"name": "s", "type": "string"},
            {"name": "b", "type": "bytes"},
            {"name": "flag", "type": "boolean"},
            {"name": "maybe", "type": ["null", "long"]},
            {"name": "tags", "type": {"type": "array", "items": "string"}},
            {"name": "kv", "type": {"type": "map", "values": "long"}},
            {"name": "color", "type": {"type": "enum", "name": "c",
                                       "symbols": ["RED", "BLUE"]}},
            {"name": "fix", "type": {"type": "fixed", "name": "fx",
                                     "size": 4}},
            {"name": "nested", "type": {
                "type": "record", "name": "inner", "fields": [
                    {"name": "x", "type": "double"}]}},
        ],
    }
    rows = [
        {"i": -(2 ** 40), "f": 1.5, "s": "héllo", "b": b"\x00\xff",
         "flag": True, "maybe": None, "tags": ["a", "b"],
         "kv": {"k": 7}, "color": "BLUE", "fix": b"abcd",
         "nested": {"x": 2.25}},
        {"i": 3, "f": -0.25, "s": "", "b": b"", "flag": False,
         "maybe": 42, "tags": [], "kv": {}, "color": "RED",
         "fix": b"wxyz", "nested": {"x": 0.0}},
    ]
    path = str(tmp_path / "t.avro")
    avro.write_file(path, schema, rows, codec="deflate")
    assert list(avro.read_file(path)) == rows


def test_avro_corrupt_sync_detected(tmp_path):
    schema = {"type": "record", "name": "r",
              "fields": [{"name": "i", "type": "long"}]}
    path = str(tmp_path / "t.avro")
    avro.write_file(path, schema, [{"i": 1}])
    raw = bytearray(open(path, "rb").read())
    raw[-1] ^= 0xFF  # flip a sync-marker byte
    open(path, "wb").write(bytes(raw))
    with pytest.raises(ValueError, match="sync marker"):
        list(avro.read_file(path))


def test_avro_roundtrip_through_dataset(tmp_path):
    ds = rd.from_items(
        [{"id": i, "name": f"row{i}", "score": i * 0.5}
         for i in range(100)])
    out = str(tmp_path / "avro_out")
    files = ds.write_avro(out)
    assert files and all(f.endswith(".avro") for f in files)
    back = rd.read_avro(out)
    rows = sorted(back.take_all(), key=lambda r: r["id"])
    assert len(rows) == 100
    assert rows[3] == {"id": 3, "name": "row3", "score": 1.5}


def test_avro_block_boundaries(tmp_path):
    schema = avro.infer_schema([{"n": 0}])
    path = str(tmp_path / "many.avro")
    avro.write_file(path, schema, ({"n": i} for i in range(10_000)),
                    block_rows=777)
    got = [r["n"] for r in avro.read_file(path)]
    assert got == list(range(10_000))


def test_avro_ragged_rows_roundtrip(tmp_path):
    """infer_schema + write_file honor the documented contract: fields
    missing in some rows become nullable unions and encode the null
    branch."""
    rows = [{"a": 1}, {"a": 2, "b": 3}]
    schema = avro.infer_schema(rows)
    path = str(tmp_path / "ragged.avro")
    avro.write_file(path, schema, rows)
    back = list(avro.read_file(path))
    assert back == [{"a": 1, "b": None}, {"a": 2, "b": 3}]


def test_avro_union_of_complex_types(tmp_path):
    """A column mixing an array with another type unions REAL schema
    values (dicts), not JSON strings, and round-trips."""
    rows = [{"a": [1, 2]}, {"a": "x"}]
    schema = avro.infer_schema(rows)
    (branch,) = [f["type"] for f in schema["fields"] if f["name"] == "a"]
    assert isinstance(branch, list)
    assert {"type": "array", "items": "long"} in branch
    assert "string" in branch
    path = str(tmp_path / "union.avro")
    avro.write_file(path, schema, rows)
    assert list(avro.read_file(path)) == rows


def test_avro_infer_schema_nullable():
    rows = [{"a": 1, "b": "x"}, {"a": None, "b": "y", "c": 2.0}]
    schema = avro.infer_schema(rows)
    by_name = {f["name"]: f["type"] for f in schema["fields"]}
    assert by_name["a"] in (["null", "long"], ["long", "null"])
    assert by_name["b"] == "string"
    assert "null" in by_name["c"]  # missing in row 0 -> nullable


# ---------------------------------------------------------------------------
# WebDataset
# ---------------------------------------------------------------------------


def _make_shard(tmp_path, n=6):
    ds = rd.from_items([
        {"__key__": f"sample{i:03d}", "txt": f"caption {i}", "cls": i % 3,
         "json": {"idx": i}, "npy": np.arange(4) + i}
        for i in range(n)])
    return ds.write_webdataset(str(tmp_path / "wds"))


def test_webdataset_roundtrip(tmp_path):
    files = _make_shard(tmp_path)
    assert all(f.endswith(".tar") for f in files)
    rows = sorted(rd.read_webdataset(files).take_all(),
                  key=lambda r: r["__key__"])
    assert len(rows) == 6
    r2 = rows[2]
    assert r2["__key__"] == "sample002"
    assert r2["txt"] == "caption 2"
    assert int(r2["cls"]) == 2
    assert r2["json"] == {"idx": 2}
    np.testing.assert_array_equal(np.asarray(r2["npy"]),
                                  np.arange(4) + 2)


def test_webdataset_suffix_filter_and_raw(tmp_path):
    files = _make_shard(tmp_path, n=3)
    rows = rd.read_webdataset(files, suffixes=["txt"]).take_all()
    assert all(set(r) == {"__key__", "txt"} for r in rows)
    raw = rd.read_webdataset(files, suffixes=["txt"],
                             decoder=False).take_all()
    assert all(isinstance(r["txt"], bytes) for r in raw)


def test_webdataset_ragged_rows_skip_none(tmp_path):
    """Columns absent in a row (None after block materialization) skip
    the tar member instead of crashing or writing 'None'."""
    files = rd.from_items([
        {"__key__": "a", "txt": "x"},
        {"__key__": "b", "txt": "y", "cls": 1},
    ]).write_webdataset(str(tmp_path / "ragged"))
    rows = {r["__key__"]: r for r in rd.read_webdataset(files).take_all()}
    assert "cls" not in rows["a"] and rows["a"]["txt"] == "x"
    assert int(rows["b"]["cls"]) == 1


def test_webdataset_dotted_directory_keys(tmp_path):
    """Member paths with dotted directory names split key/suffix on the
    BASENAME (reference _base_plus_ext), not the first dot of the path."""
    import io
    import tarfile

    shard = str(tmp_path / "dotted.tar")
    with tarfile.open(shard, "w") as tar:
        for key in ("data.v1/s1", "data.v1/s2"):
            for suffix, payload in (("txt", b"hello"), ("cls", b"7")):
                info = tarfile.TarInfo(name=f"{key}.{suffix}")
                info.size = len(payload)
                tar.addfile(info, io.BytesIO(payload))
    rows = sorted(rd.read_webdataset(shard).take_all(),
                  key=lambda r: r["__key__"])
    assert [r["__key__"] for r in rows] == ["data.v1/s1", "data.v1/s2"]
    assert all(set(r) == {"__key__", "txt", "cls"} for r in rows)
    assert rows[0]["txt"] == "hello" and int(rows[1]["cls"]) == 7


def test_webdataset_custom_decoder(tmp_path):
    files = _make_shard(tmp_path, n=2)
    rows = rd.read_webdataset(
        files, suffixes=["cls"],
        decoder=lambda suffix, data: f"{suffix}:{data.decode()}"
    ).take_all()
    assert sorted(r["cls"] for r in rows) == ["cls:0", "cls:1"]


# ---------------------------------------------------------------------------
# Ref-based constructors
# ---------------------------------------------------------------------------


def test_from_arrow_refs():
    t1 = pa.table({"a": [1, 2]})
    t2 = pa.table({"a": [3]})
    ds = rd.from_arrow_refs([ray_tpu.put(t1), ray_tpu.put(t2)])
    assert sorted(r["a"] for r in ds.take_all()) == [1, 2, 3]


def test_from_pandas_refs():
    import pandas as pd

    df = pd.DataFrame({"x": [10, 20], "y": ["a", "b"]})
    ds = rd.from_pandas_refs(ray_tpu.put(df))
    assert ds.count() == 2
    assert sorted(r["x"] for r in ds.take_all()) == [10, 20]


def test_from_numpy_refs():
    refs = [ray_tpu.put(np.arange(3)), ray_tpu.put(np.arange(3, 5))]
    ds = rd.from_numpy_refs(refs, column="v")
    assert sorted(r["v"] for r in ds.take_all()) == [0, 1, 2, 3, 4]


def test_from_blocks_and_parquet_bulk(tmp_path):
    ds = rd.from_blocks([pa.table({"a": [1]}), pa.table({"a": [2]})])
    assert ds.count() == 2
    files = rd.from_items(
        [{"a": i} for i in range(10)]).write_parquet(str(tmp_path / "p"))
    assert rd.read_parquet_bulk(files).count() == 10


# ---------------------------------------------------------------------------
# External connectors against protocol-faithful stubs
#
# Stub classes live in this (worker-unimportable) test module, so these
# tests execute the ReadTasks driver-side — the same style as the tune
# external-searcher stub tests.  The remote execution path is covered by
# the real readers above.
# ---------------------------------------------------------------------------


def _rows_of(datasource, parallelism=4):
    from ray_tpu.data.block import BlockAccessor

    rows = []
    for task in datasource.get_read_tasks(parallelism):
        for block in task():
            rows.extend(BlockAccessor(block).iter_rows())
    return rows


class _Fragment:
    def __init__(self, fid, table):
        self.fragment_id = fid
        self._table = table

    def to_table(self, columns=None, filter=None):
        t = self._table
        if filter is not None:
            import pyarrow.compute as pc

            # stub supports the single filter shape the test sends
            t = t.filter(pc.field("a") > 1)
        if columns:
            t = t.select(columns)
        return t


def _lance_stub():
    tables = [pa.table({"a": [1, 2], "b": ["x", "y"]}),
              pa.table({"a": [3], "b": ["z"]})]

    class _LanceDS:
        def get_fragments(self):
            return [_Fragment(i, t) for i, t in enumerate(tables)]

        def to_table(self, columns=None, filter=None):
            return pa.concat_tables(tables)

    mod = types.ModuleType("lance")
    mod.dataset = lambda uri: _LanceDS()
    return mod


def test_read_lance_stub():
    from ray_tpu.data.external import LanceDatasource

    src = LanceDatasource("mem://t", _module=_lance_stub())
    assert sorted(r["a"] for r in _rows_of(src)) == [1, 2, 3]
    src = LanceDatasource("mem://t", columns=["b"], _module=_lance_stub())
    rows = _rows_of(src)
    assert sorted(r["b"] for r in rows) == ["x", "y", "z"]
    assert all(set(r) == {"b"} for r in rows)
    src = LanceDatasource("mem://t", filter="a > 1", _module=_lance_stub())
    assert sorted(r["a"] for r in _rows_of(src)) == [2, 3]


def test_read_bigquery_stub():
    table = pa.table({"n": [1, 2, 3]})

    class _Result:
        def to_arrow(self):
            return table

    class _Client:
        def __init__(self, project=None):
            self.project = project

        def query(self, q):
            assert "SELECT" in q

            class _Job:
                def result(self):
                    return _Result()

            return _Job()

        def list_rows(self, fq_table):
            assert fq_table == "proj.ds.t"
            return _Result()

    from ray_tpu.data.external import BigQueryDatasource

    mod = types.ModuleType("google.cloud.bigquery")
    mod.Client = _Client
    src = BigQueryDatasource("proj", dataset="ds.t", _module=mod)
    assert sorted(r["n"] for r in _rows_of(src)) == [1, 2, 3]
    src = BigQueryDatasource("proj", query="SELECT n FROM t", _module=mod)
    assert len(_rows_of(src)) == 3
    with pytest.raises(ValueError, match="exactly one"):
        BigQueryDatasource("proj", _module=mod)


def test_read_mongo_stub():
    docs = [{"_id": "oid1", "v": 1}, {"_id": "oid2", "v": 2}]

    class _Coll:
        def aggregate(self, pipeline):
            assert isinstance(pipeline, list)
            return iter(docs)

    class _Client:
        def __init__(self, uri):
            assert uri.startswith("mongodb://")

        def __getitem__(self, name):
            return {"c": _Coll()} if name == "d" else None

        def close(self):
            pass

    from ray_tpu.data.external import MongoDatasource

    mod = types.ModuleType("pymongo")
    mod.MongoClient = _Client
    src = MongoDatasource("mongodb://h", "d", "c", _module=mod)
    rows = _rows_of(src)
    assert sorted(r["v"] for r in rows) == [1, 2]
    assert all("_id" not in r for r in rows)


def test_delta_sharing_stub():
    import pandas as pd

    from ray_tpu.data.external import DeltaSharingDatasource

    mod = types.ModuleType("delta_sharing")
    calls = []

    def load_as_pandas(url, limit=None, version=None):
        calls.append(url)
        return pd.DataFrame({"q": [5, 6]})

    mod.load_as_pandas = load_as_pandas
    src = DeltaSharingDatasource("prof#share.schema.t", _module=mod)
    assert not calls, "download must be deferred into the ReadTask"
    assert sorted(r["q"] for r in _rows_of(src)) == [5, 6]
    assert calls == ["prof#share.schema.t"]


def test_databricks_stub(monkeypatch):
    monkeypatch.setenv("DATABRICKS_HOST", "h.example")
    monkeypatch.setenv("DATABRICKS_TOKEN", "tok")

    class _Cursor:
        description = [("v",)]

        def execute(self, sql):
            assert sql == "SELECT * FROM cat.sch.t"

        def fetchall(self):
            return [(1,), (2,)]

    class _Conn:
        def cursor(self):
            return _Cursor()

        def close(self):
            pass

    mod = types.ModuleType("databricks.sql")
    mod.connect = lambda **kw: _Conn()
    ds = rd.read_databricks_tables(
        warehouse_id="w1", table="t", catalog="cat", schema="sch",
        _module=mod)
    # the stub module can't be unpickled by workers: run the SQL
    # datasource's tasks driver-side
    assert sorted(r["v"] for r in _rows_of(ds._terminal.datasource)) == [1, 2]


def test_from_huggingface_duck():
    table = pa.table({"text": ["a", "b"]})

    class _Data:
        def __init__(self):
            self.table = table

    class _HFDataset:
        data = _Data()

    # .combine_chunks() exists on real pa.Table already
    ds = rd.from_huggingface(_HFDataset())
    assert sorted(r["text"] for r in ds.take_all()) == ["a", "b"]
    with pytest.raises(TypeError, match="datasets.Dataset"):
        rd.from_huggingface(object())

    # A select()-ed HF dataset carries _indices while .data still holds
    # the FULL table: must materialize through to_pandas, not the
    # stale zero-copy table.
    import pandas as pd

    class _Selected:
        data = _Data()
        _indices = object()  # any non-None marker

        def to_pandas(self):
            return pd.DataFrame({"text": ["b"]})

    sel = rd.from_huggingface(_Selected())
    assert [r["text"] for r in sel.take_all()] == ["b"]


def test_from_dask_spark_modin_mars_duck():
    import pandas as pd

    part = pd.DataFrame({"z": [1]})

    class _Delayed:
        def compute(self):
            return part

    class _Dask:
        def to_delayed(self):
            return [_Delayed(), _Delayed()]

    assert rd.from_dask(_Dask()).count() == 2

    class _Spark:
        def toPandas(self):
            return pd.DataFrame({"z": [1, 2, 3]})

    assert rd.from_spark(_Spark()).count() == 3

    class _Modin:
        def _to_pandas(self):
            return part

    assert rd.from_modin(_Modin()).count() == 1

    class _MarsExecuted:
        def to_pandas(self):
            return part

    class _Mars:
        def execute(self):
            return _MarsExecuted()

    assert rd.from_mars(_Mars()).count() == 1


def test_from_tf_duck():
    class _TF:
        def as_numpy_iterator(self):
            yield {"x": np.float32(1.0), "y": np.int64(2)}
            yield {"x": np.float32(3.0), "y": np.int64(4)}

    ds = rd.from_tf(_TF())
    rows = sorted(ds.take_all(), key=lambda r: r["y"])
    assert rows[0]["x"] == pytest.approx(1.0)
    assert rows[1]["y"] == 4

    class _TFTuples:
        def as_numpy_iterator(self):
            yield (np.int64(1), np.int64(2))

    assert rd.from_tf(_TFTuples()).take_all()[0]["col_1"] == 2


def test_write_numpy_roundtrip(tmp_path):
    ds = rd.from_numpy(np.arange(12).reshape(12, 1), column="v")
    files = ds.write_numpy(str(tmp_path / "np"), column="v")
    back = rd.read_numpy(files, column="v")
    got = np.sort(np.concatenate(
        [np.asarray(r["v"]).ravel() for r in back.take_all()]))
    np.testing.assert_array_equal(got, np.arange(12))


def test_write_images_roundtrip(tmp_path):
    imgs = (np.arange(4 * 5 * 3, dtype=np.uint8)
            .reshape(1, 4, 5, 3).repeat(3, axis=0))
    ds = rd.from_numpy(imgs, column="image")
    files = ds.write_images(str(tmp_path / "imgs"))
    assert all(f.endswith(".png") for f in files)
    back = rd.read_images(str(tmp_path / "imgs")).take_all()
    assert len(back) == 3
    np.testing.assert_array_equal(np.asarray(back[0]["image"]), imgs[0])


def test_write_sql_roundtrip(tmp_path):
    import sqlite3

    db = str(tmp_path / "w.db")
    conn = sqlite3.connect(db)
    conn.execute("CREATE TABLE t (a INTEGER, b TEXT)")
    conn.commit()
    conn.close()

    def factory(db=db):
        import sqlite3

        return sqlite3.connect(db)

    ds = rd.from_items([{"a": i, "b": f"s{i}"} for i in range(7)])
    parts = ds.write_sql("INSERT INTO t VALUES (?, ?)", factory)
    assert parts
    back = rd.read_sql("SELECT a, b FROM t ORDER BY a", factory)
    rows = back.take_all()
    assert len(rows) == 7 and rows[3] == {"a": 3, "b": "s3"}


def test_write_images_skips_empty_blocks(tmp_path):
    """Blocks emptied by a filter must not fabricate paths to files
    that were never written."""
    imgs = np.zeros((4, 4, 5, 3), np.uint8)
    ds = rd.from_numpy(imgs, column="image").filter(lambda r: False)
    files = ds.write_images(str(tmp_path / "none"))
    assert files == []


def test_write_numpy_ragged_raises(tmp_path):
    rows = [{"v": np.zeros(2)}, {"v": np.zeros(3)}]
    ds = rd.from_items(rows, parallelism=1)  # one ragged block
    with pytest.raises(Exception, match="write_parquet"):
        ds.write_numpy(str(tmp_path / "rg"), column="v")


def test_catalog_ndarray_model_config():
    import gymnasium as gym

    from ray_tpu.rl import Catalog

    spec = Catalog(gym.spaces.Box(-1, 1, (4,), np.float32),
                   gym.spaces.Discrete(2),
                   {"fcnet_hiddens": np.array([32, 16])}
                   ).build_module_spec()
    assert tuple(spec.hidden_sizes) == (32, 16)


def test_write_mongo_bigquery_stubs():
    from ray_tpu.data.block import batch_to_block
    from ray_tpu.data.datasource import (
        write_block_bigquery,
        write_block_mongo,
    )

    block = batch_to_block({"x": np.asarray([1, 2, 3])})
    inserted = []

    class _Coll:
        def insert_many(self, docs):
            inserted.extend(docs)

    class _Mongo:
        def __init__(self, uri):
            pass

        def __getitem__(self, name):
            return {"c": _Coll()}

        def close(self):
            pass

    mod = types.ModuleType("pymongo")
    mod.MongoClient = _Mongo
    out = write_block_mongo(block, "", 0, uri="mongodb://h",
                            database="d", collection="c", _module=mod)
    assert out.endswith(":3") and [d["x"] for d in inserted] == [1, 2, 3]

    loaded = []

    class _Job:
        def result(self):
            return None

    class _BQClient:
        def __init__(self, project=None):
            pass

        def load_table_from_dataframe(self, df, table):
            loaded.append((table, len(df)))
            return _Job()

    bq = types.ModuleType("google.cloud.bigquery")
    bq.Client = _BQClient
    out = write_block_bigquery(block, "", 0, project_id="p",
                               dataset="d.t", _module=bq)
    assert out.endswith(":3") and loaded == [("p.d.t", 3)]


def test_split_at_indices_and_proportionately():
    ds = rd.range(10)
    a, b, c = ds.split_at_indices([3, 7])
    assert [d.count() for d in (a, b, c)] == [3, 4, 3]
    assert sorted(r["id"] for r in b.take_all()) == [3, 4, 5, 6]
    with pytest.raises(ValueError, match="sorted"):
        ds.split_at_indices([7, 3])

    # Read tasks finish in any order on a busy host: the cut is made in
    # source order whatever order the bundles arrived in.
    mat = rd.range(10).materialize()
    assert len(mat._materialized) > 1
    mat._materialized.reverse()
    a, b, c = mat.split_at_indices([3, 7])
    assert sorted(r["id"] for r in b.take_all()) == [3, 4, 5, 6]

    x, y, z = rd.range(20).split_proportionately([0.25, 0.5])
    assert [d.count() for d in (x, y, z)] == [5, 10, 5]
    with pytest.raises(ValueError, match="less than 1"):
        rd.range(4).split_proportionately([0.5, 0.5])


def test_train_test_split():
    train, test = rd.range(100).train_test_split(0.2)
    assert train.count() == 80 and test.count() == 20
    # Non-exact fraction rounds like the reference's
    # split_proportionately([1 - test_size]): train = int(10 * 0.75).
    train, test = rd.range(10).train_test_split(0.25)
    assert train.count() == 7 and test.count() == 3
    # absolute count + shuffle covers the whole range exactly once
    train, test = rd.range(10).train_test_split(3, shuffle=True, seed=0)
    ids = sorted(r["id"] for r in train.take_all()) + \
        sorted(r["id"] for r in test.take_all())
    assert sorted(ids) == list(range(10)) and test.count() == 3


def test_unique_and_size_and_block_order():
    ds = rd.from_items([{"v": i % 3, "w": "x"} for i in range(12)])
    assert sorted(ds.unique("v")) == [0, 1, 2]
    assert ds.size_bytes() > 0
    shuffled = rd.range(16).randomize_block_order(seed=1)
    assert sorted(r["id"] for r in shuffled.take_all()) == \
        list(range(16))
    # List-valued columns come back as the ORIGINAL lists, and struct
    # (dict) values dedupe instead of raising unhashable-type.
    tags = rd.from_items([{"t": [1, 2]}, {"t": [1, 2]}, {"t": [3]}])
    assert [1, 2] in tags.unique("t") and len(tags.unique("t")) == 2
    structs = rd.from_items([{"s": {"a": 1}}, {"s": {"a": 1}},
                             {"s": {"a": 2}}])
    assert len(structs.unique("s")) == 2


def test_map_groups():
    ds = rd.from_items([{"k": i % 3, "v": float(i)} for i in range(12)])

    def top1(df):  # pandas group in, DataFrame out
        return df.nlargest(1, "v")

    rows = sorted(ds.groupby("k").map_groups(top1).take_all(),
                  key=lambda r: r["k"])
    assert [(r["k"], r["v"]) for r in rows] == [(0, 9.0), (1, 10.0),
                                               (2, 11.0)]

    def spread(batch):  # numpy group in, dict-batch out
        return {"k": batch["k"][:1],
                "spread": [float(batch["v"].max() - batch["v"].min())]}

    rows = sorted(ds.groupby("k").map_groups(
        spread, batch_format="numpy").take_all(), key=lambda r: r["k"])
    assert all(r["spread"] == 9.0 for r in rows) and len(rows) == 3

    # None drops a group; list-of-rows output works.
    def keep_even(df):
        if int(df["k"].iloc[0]) % 2:
            return None
        return [{"k": int(df["k"].iloc[0]), "n": len(df)}]

    rows = ds.groupby("k").map_groups(keep_even).take_all()
    assert sorted(r["k"] for r in rows) == [0, 2]

    with pytest.raises(ValueError, match="groupby key"):
        ds.groupby(None).map_groups(top1)


def test_show_and_empty_bridges(capsys):
    rd.range(3).show()
    out = capsys.readouterr().out
    assert out.count("{") == 3 and "'id': 0" in out

    # Empty dataset through the bridges: defined, not crashing.
    empty = rd.from_items([{"a": 1}]).filter(lambda r: False)
    refs = empty.to_arrow_refs()
    assert all(ray_tpu.get(r).num_rows == 0 for r in refs)
    assert empty.size_bytes() >= 0

    made = []
    mod = types.ModuleType("dask.dataframe")
    mod.from_pandas = lambda df, npartitions=1: made.append(len(df)) or "p"
    mod.concat = lambda parts: "df"
    empty.to_dask(_module=mod)  # hits the no-blocks fallback
    assert made == [0]


def test_map_groups_under_pandas_block_format():
    """map_groups DataFrame outputs normalize through batch_to_block,
    so a pandas-format pipeline keeps pandas blocks."""
    import subprocess
    import sys

    code = """
import ray_tpu, ray_tpu.data as rd
from ray_tpu.data.context import DataContext
DataContext.get_current().block_format = "pandas"
ray_tpu.init(num_cpus=2)
ds = rd.from_items([{"k": i % 2, "v": i} for i in range(6)])
rows = ds.groupby("k").map_groups(lambda df: df.nlargest(1, "v"))
out = sorted((r["k"], r["v"]) for r in rows.take_all())
assert out == [(0, 4), (1, 5)], out
from ray_tpu.data.block import PandasBlock
blocks = list(rows.iter_internal_blocks())
assert blocks and all(isinstance(b, PandasBlock) for b in blocks), blocks
print("OK")
"""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", code], cwd=repo,
                         capture_output=True, text=True, timeout=120)
    assert "OK" in res.stdout, res.stdout + res.stderr


def test_split_equal_truncates_remainder():
    parts = rd.range(10).split(3, equal=True)
    assert [p.count() for p in parts] == [3, 3, 3]
    parts = rd.range(10).split(3)
    assert sum(p.count() for p in parts) == 10


def test_to_refs_roundtrip():
    ds = rd.from_items([{"a": i} for i in range(6)])
    back = rd.from_arrow_refs(ds.to_arrow_refs())
    assert sorted(r["a"] for r in back.take_all()) == list(range(6))
    back = rd.from_pandas_refs(ds.to_pandas_refs())
    assert back.count() == 6
    refs = rd.from_numpy(np.arange(5), column="v").to_numpy_refs(
        column="v")
    vals = np.sort(np.concatenate([np.asarray(ray_tpu.get(r))
                                   for r in refs]))
    np.testing.assert_array_equal(vals, np.arange(5))


def test_to_dataframe_bridges_stubs():
    import pandas as pd

    ds = rd.from_items([{"q": 1}, {"q": 2}])

    concat_args = []
    mod = types.ModuleType("dask.dataframe")
    mod.from_pandas = lambda df, npartitions=1: ("part", len(df))
    mod.concat = lambda parts: concat_args.append(parts) or "dask-df"
    assert ds.to_dask(_module=mod) == "dask-df"
    assert len(concat_args[0]) >= 1

    mpd = types.ModuleType("modin.pandas")
    mpd.DataFrame = lambda df: ("modin", len(df))
    assert ds.to_modin(_module=mpd) == ("modin", 2)

    class _Spark:
        def createDataFrame(self, df):
            return ("spark", len(df))

    assert ds.to_spark(_Spark()) == ("spark", 2)
    with pytest.raises(TypeError, match="SparkSession"):
        ds.to_spark(object())

    captured = {}
    tf = types.ModuleType("tensorflow")
    tf.data = types.SimpleNamespace(Dataset=types.SimpleNamespace(
        from_tensor_slices=lambda batch: captured.update(batch) or "tfds"))
    assert ds.to_tf(_module=tf) == "tfds"
    np.testing.assert_array_equal(np.sort(captured["q"]), [1, 2])


def test_missing_module_guidance():
    with pytest.raises(ImportError, match="read_parquet"):
        rd.read_lance("mem://t")
    try:
        import google.cloud.bigquery  # noqa: F401  (present in image)
    except ImportError:
        with pytest.raises(ImportError, match="read_avro"):
            rd.read_bigquery("p", dataset="d.t")
    with pytest.raises(ImportError, match="read_json"):
        rd.read_mongo("mongodb://h", "d", "c")
