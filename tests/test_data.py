"""Data tests — mirrors python/ray/data/tests strategy (SURVEY §4.3):
small in-memory blocks, operator-level coverage, streaming executor."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd


# ---------- pure block machinery (no cluster) ----------

def test_block_normalize_and_accessor():
    from ray_tpu.data.block import BlockAccessor

    acc = BlockAccessor.for_block({"a": np.arange(5), "b": list("vwxyz")})
    assert acc.num_rows() == 5
    out = acc.to_numpy()
    np.testing.assert_array_equal(out["a"], np.arange(5))
    rows = list(acc.iter_rows())
    assert rows[0] == {"a": 0, "b": "v"}


def test_block_tensor_columns():
    from ray_tpu.data.block import BlockAccessor

    arr = np.arange(24, dtype=np.float32).reshape(6, 4)
    acc = BlockAccessor.for_block({"x": arr})
    out = acc.to_numpy()["x"]
    np.testing.assert_array_equal(out, arr)


@pytest.mark.parametrize("shape", [(6, 4), (6, 2, 3)])
def test_block_tensor_column_of_a_slice_is_an_ndarray(shape):
    """A batch is a slice of its block: its tensor column comes back as the
    stacked ndarray of ITS rows, not as an object array of Python lists
    (the fallback every sliced batch took: 2.4 ms a batch of 2 x 4097
    ids, inside every training step that reads ``iter_batches``)."""
    from ray_tpu.data.block import BlockAccessor

    arr = np.arange(np.prod(shape), dtype=np.int64).reshape(shape)
    acc = BlockAccessor.for_block({"x": arr})
    out = BlockAccessor.for_block(acc.slice(2, 5)).to_numpy()["x"]
    assert out.dtype == np.int64 and out.shape == (3, *shape[1:])
    np.testing.assert_array_equal(out, arr[2:5])


def test_plan_fusion():
    from ray_tpu.data._internal.plan import (
        Filter, LogicalPlan, MapRows, MapStage, plan_stages, RandomShuffle, Read,
    )

    plan = LogicalPlan(
        [Read(), MapRows(fn=lambda r: r), Filter(fn=lambda r: True),
         RandomShuffle(), MapRows(fn=lambda r: r)]
    )
    stages = plan_stages(plan)
    # Read | fused(Map+Filter) | shuffle | Map
    assert len(stages) == 4
    assert isinstance(stages[1], MapStage)
    assert len(stages[1].ops) == 2


# ---------- end-to-end on the shared cluster ----------

def test_range_map_filter_count(ray_start_shared):
    ds = rd.range(100, parallelism=4)
    out = (
        ds.map(lambda row: {"id": row["id"] * 2})
        .filter(lambda row: row["id"] % 4 == 0)
        .count()
    )
    assert out == 50


def test_map_batches_numpy(ray_start_shared):
    ds = rd.range(32, parallelism=2).map_batches(
        lambda batch: {"sq": batch["id"] ** 2}
    )
    rows = ds.take_all()
    assert sorted(r["sq"] for r in rows) == [i * i for i in range(32)]


def test_map_batches_actor_compute(ray_start_shared):
    class AddState:
        def __init__(self):
            self.offset = 1000

        def __call__(self, batch):
            return {"y": batch["id"] + self.offset}

    ds = rd.range(20, parallelism=2).map_batches(AddState, batch_size=5)
    values = sorted(r["y"] for r in ds.take_all())
    assert values == [1000 + i for i in range(20)]


def test_flat_map_and_limit(ray_start_shared):
    ds = rd.from_items([{"x": 1}, {"x": 2}]).flat_map(
        lambda row: [{"x": row["x"]}, {"x": row["x"] * 10}]
    )
    assert ds.count() == 4
    assert rd.range(50).limit(7).count() == 7


def test_repartition_and_num_blocks(ray_start_shared):
    ds = rd.range(100, parallelism=8).repartition(3).materialize()
    assert ds.num_blocks() == 3
    assert ds.count() == 100


def test_random_shuffle_preserves_rows(ray_start_shared):
    ds = rd.range(64, parallelism=4).random_shuffle(seed=0)
    ids = [r["id"] for r in ds.take_all()]
    assert sorted(ids) == list(range(64))
    assert ids != list(range(64))  # overwhelmingly likely shuffled


def test_sort(ray_start_shared):
    rng = np.random.default_rng(7)
    values = rng.permutation(50)
    ds = rd.from_items([{"v": int(v)} for v in values]).sort("v")
    out = [r["v"] for r in ds.take_all()]
    assert out == sorted(out)
    out_desc = [
        r["v"]
        for r in rd.from_items([{"v": int(v)} for v in values])
        .sort("v", descending=True)
        .take_all()
    ]
    assert out_desc == sorted(out_desc, reverse=True)


def test_groupby_aggregate(ray_start_shared):
    rows = [{"k": i % 3, "v": float(i)} for i in range(30)]
    ds = rd.from_items(rows).groupby("k").sum("v")
    out = {r["k"]: r["sum(v)"] for r in ds.take_all()}
    expected = {}
    for row in rows:
        expected[row["k"]] = expected.get(row["k"], 0.0) + row["v"]
    assert out == expected


def test_global_aggregates(ray_start_shared):
    ds = rd.range(10)
    assert ds.sum("id") == 45
    assert ds.min("id") == 0
    assert ds.max("id") == 9
    assert ds.mean("id") == pytest.approx(4.5)


def test_zip_and_union(ray_start_shared):
    a = rd.from_items([{"a": i} for i in range(6)])
    b = rd.from_items([{"b": i * 2} for i in range(6)])
    zipped = a.zip(b)
    rows = zipped.take_all()
    assert len(rows) == 6
    assert all(r["b"] == r["a"] * 2 for r in rows)

    u = rd.from_items([{"x": 1}]).union(rd.from_items([{"x": 2}]))
    assert u.count() == 2


def test_iter_batches_formats_and_sizes(ray_start_shared):
    ds = rd.range(100, parallelism=5)
    batches = list(ds.iter_batches(batch_size=32, batch_format="numpy"))
    sizes = [len(b["id"]) for b in batches]
    assert sum(sizes) == 100
    assert all(s == 32 for s in sizes[:-1])
    pdf = next(iter(ds.iter_batches(batch_size=10, batch_format="pandas")))
    assert list(pdf.columns) == ["id"]
    tb = next(iter(ds.iter_torch_batches(batch_size=10)))
    import torch

    assert isinstance(tb["id"], torch.Tensor)


def test_streaming_split(ray_start_shared):
    ds = rd.range(40, parallelism=4).materialize()
    shards = ds.streaming_split(2)
    seen = []
    for shard in shards:
        for batch in shard.iter_batches(batch_size=None):
            seen += batch["id"].tolist()
    assert sorted(seen) == list(range(40))


def test_read_write_parquet_csv_json(ray_start_shared, tmp_path):
    ds = rd.range(25, parallelism=2).map(lambda r: {"id": r["id"], "s": str(r["id"])})
    for fmt in ("parquet", "csv", "json"):
        out_dir = str(tmp_path / fmt)
        getattr(ds, f"write_{fmt}")(out_dir)
        back = getattr(rd, f"read_{fmt}")(out_dir)
        assert back.count() == 25
        assert sorted(r["id"] for r in back.take_all()) == list(range(25))


def test_read_text_and_numpy(ray_start_shared, tmp_path):
    p = tmp_path / "t.txt"
    p.write_text("alpha\nbeta\ngamma\n")
    ds = rd.read_text(str(p))
    assert [r["text"] for r in ds.take_all()] == ["alpha", "beta", "gamma"]

    np.save(tmp_path / "a.npy", np.arange(8))
    nds = rd.read_numpy(str(tmp_path / "a.npy"))
    assert nds.count() == 8


def test_read_images(ray_start_shared, tmp_path):
    from PIL import Image

    for i in range(3):
        Image.new("RGB", (8, 8), color=(i * 20, 0, 0)).save(tmp_path / f"im{i}.png")
    ds = rd.read_images(str(tmp_path), size=(4, 4))
    rows = ds.take_all()
    assert len(rows) == 3
    assert np.asarray(rows[0]["image"]).shape == (4, 4, 3)


def test_select_drop_add_columns(ray_start_shared):
    ds = rd.from_items([{"a": 1, "b": 2, "c": 3}] * 4)
    assert ds.select_columns(["a", "b"]).columns() == ["a", "b"]
    assert ds.drop_columns(["c"]).columns() == ["a", "b"]

    import pyarrow.compute as pc

    with_col = ds.add_column("d", lambda t: pc.add(t.column("a"), t.column("b")))
    assert with_col.take(1)[0]["d"] == 3


def test_dataset_stats_and_schema(ray_start_shared):
    ds = rd.range(10).map_batches(lambda b: b).materialize()
    report = ds.stats()
    assert "MapStage" in report or "MapBatches" in report
    assert ds.schema() is not None


def test_train_ingest_integration(ray_start_shared, tmp_path):
    """Dataset → JaxTrainer via streaming_split (SURVEY §3.3 ingest path)."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ds = rd.range(64, parallelism=4)

    def loop(config):
        from ray_tpu import train

        shard = train.get_dataset_shard("train")
        total = 0
        for batch in shard.iter_batches(batch_size=8):
            total += int(batch["id"].sum())
        train.report({"total": total})

    result = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="ingest", storage_path=str(tmp_path)),
        datasets={"train": ds},
    ).fit()
    assert result.error is None, (
        f"{result.error!r}\n{getattr(result.error, 'worker_traceback', '')}"
    )
    # Both workers together saw every row exactly once.
    assert result.metrics["total"] <= sum(range(64))


def test_groupby_string_keys_cross_process(ray_start_shared):
    """Regression: groupby partitioning must use a deterministic hash —
    builtin hash() is per-process salted for str, so the same key could
    land in different partitions from different map workers, yielding
    duplicate keys with partial aggregates."""
    rows = [{"k": f"key-{i % 5}", "v": 1.0} for i in range(40)]
    # Enough blocks that _split_block runs in multiple worker processes.
    ds = rd.from_items(rows, parallelism=8).groupby("k").sum("v")
    out = {r["k"]: r["sum(v)"] for r in ds.take_all()}
    assert len(out) == 5, out
    assert all(v == 8.0 for v in out.values()), out


# ---------- round 3: stats depth, tfrecords, datasource/datasink ----------

def test_dataset_stats_per_operator(ray_start_shared):
    import ray_tpu.data as rd

    ds = rd.range(1000, parallelism=4).map_batches(lambda b: b)
    list(ds.iter_batches(batch_size=100))
    report = ds.stats()
    # per-operator table with wall/cpu/tasks/rows/bytes columns
    assert "operator" in report and "cpu" in report and "bytes" in report
    assert "Read" in report and "MapBatches" in report
    # rows propagated through both stages
    for line in report.splitlines():
        if "MapBatches" in line:
            assert " 1000 " in line or line.rstrip().endswith("1000") or "1000" in line
    # consumption-side accounting
    assert "iterator:" in report and "wait" in report


def test_tfrecords_roundtrip(ray_start_shared, tmp_path):
    import ray_tpu.data as rd

    items = [
        {"id": i, "name": f"row-{i}", "score": float(i) / 2} for i in range(50)
    ]
    ds = rd.from_items(items)
    path = str(tmp_path / "tfr")
    ds.write_tfrecords(path)
    back = rd.read_tfrecords(path + "/*.tfrecord")
    rows = sorted(back.take_all(), key=lambda r: r["id"])
    assert len(rows) == 50
    assert rows[3]["id"] == 3
    # strings come back as bytes (tf.Example BytesList semantics)
    assert rows[3]["name"] == b"row-3"
    assert abs(rows[3]["score"] - 1.5) < 1e-6


def test_custom_datasource_roundtrip(ray_start_shared):
    import pyarrow as pa

    import ray_tpu.data as rd
    from ray_tpu.data import Datasink, Datasource, ReadTask

    class SquaresDatasource(Datasource):
        def __init__(self, n):
            self.n = n

        def get_read_tasks(self, parallelism):
            chunk = max(1, self.n // parallelism)
            tasks = []
            for start in range(0, self.n, chunk):
                end = min(start + chunk, self.n)

                def read(start=start, end=end):
                    yield pa.table({"x": list(range(start, end)),
                                    "sq": [i * i for i in range(start, end)]})

                tasks.append(ReadTask(read, num_rows=end - start))
            return tasks

    ds = rd.read_datasource(SquaresDatasource(100), parallelism=4)
    assert ds.count() == 100
    assert ds.sum("sq") == sum(i * i for i in range(100))

    class CollectingDatasink(Datasink):
        def __init__(self):
            self.started = False
            self.completed = None

        def on_write_start(self):
            self.started = True

        def write(self, blocks, ctx):
            return sum(b.num_rows for b in blocks)

        def on_write_complete(self, results):
            self.completed = sum(results)

    sink = CollectingDatasink()
    ds.write_datasink(sink)
    assert sink.started and sink.completed == 100

