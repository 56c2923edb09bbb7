"""Data layer tests: blocks, datasources, streaming execution, iteration,
Train integration.

Parity model: python/ray/data/tests/ (operator tests with in-memory blocks,
streaming executor tests — SURVEY.md §4.5).
"""

import numpy as np
import pytest

builtins_range = range  # rd.range shadows the builtin in this module's style

from ray_tpu import data as rd
from ray_tpu.data.block import (
    block_concat,
    block_from_rows,
    block_num_rows,
    block_slice,
)
from ray_tpu.data.executor import ActorPoolStrategy


class TestBlocks:
    def test_rows_roundtrip(self):
        b = block_from_rows([{"a": 1, "b": 2.0}, {"a": 3, "b": 4.0}])
        assert block_num_rows(b) == 2
        assert b["a"].tolist() == [1, 3]
        b2 = block_from_rows([10, 20, 30])
        assert b2["item"].tolist() == [10, 20, 30]

    def test_concat_slice(self):
        b1 = {"x": np.arange(3)}
        b2 = {"x": np.arange(3, 7)}
        cat = block_concat([b1, b2])
        assert block_num_rows(cat) == 7
        assert block_slice(cat, 2, 5)["x"].tolist() == [2, 3, 4]


class TestDatasetLocal:
    def test_range_count_take(self, ray_start_local):
        ds = rd.range(100, parallelism=4)
        assert ds.count() == 100
        assert ds.take(5) == [{"id": 0}, {"id": 1}, {"id": 2}, {"id": 3}, {"id": 4}]

    def test_map_batches_streaming(self, ray_start_local):
        ds = rd.range(64, parallelism=4).map_batches(
            lambda b: {"id": b["id"], "sq": b["id"] ** 2}
        )
        rows = ds.take_all()
        assert len(rows) == 64
        assert all(r["sq"] == r["id"] ** 2 for r in rows)

    def test_chained_map_and_filter(self, ray_start_local):
        ds = (
            rd.range(50, parallelism=4)
            .map_batches(lambda b: {"id": b["id"] * 2})
            .filter(lambda r: r["id"] % 4 == 0)
        )
        assert sorted(r["id"] for r in ds.take_all()) == list(range(0, 100, 4))

    def test_map_batches_with_batch_size(self, ray_start_local):
        def stamp_size(b):
            n = block_num_rows(b)
            return {"id": b["id"], "bs": np.full(n, n)}

        ds = rd.range(100, parallelism=3).map_batches(stamp_size, batch_size=32)
        rows = ds.take_all()
        assert len(rows) == 100
        # rechunked: 32/32/32/4 — every row stamped with its batch's size
        from collections import Counter

        counts = Counter(r["bs"] for r in rows)
        assert counts == {32: 96, 4: 4}

    def test_actor_pool_callable_class(self, ray_start_regular):
        class AddConst:
            def __init__(self, c):
                self.c = c

            def __call__(self, block):
                return {"id": block["id"] + self.c}

        ds = rd.range(40, parallelism=4).map_batches(
            AddConst, fn_args=(1000,), compute=ActorPoolStrategy(size=2)
        )
        rows = sorted(r["id"] for r in ds.take_all())
        assert rows == list(range(1000, 1040))

    def test_limit(self, ray_start_local):
        assert rd.range(1000, parallelism=8).limit(17).count() == 17

    def test_from_items_and_numpy(self, ray_start_local):
        ds = rd.from_items([{"v": i} for i in range(10)])
        assert ds.count() == 10
        ds2 = rd.from_numpy(np.ones((5, 3)))
        assert ds2.count() == 5
        assert ds2.take(1)[0]["data"].shape == (3,)

    def test_split_balanced(self, ray_start_local):
        shards = rd.range(103, parallelism=5).split(4)
        counts = [s.count() for s in shards]
        assert sum(counts) == 103
        assert max(counts) - min(counts) <= 3
        # shards are disjoint and cover the range
        ids = sorted(r["id"] for s in shards for r in s.take_all())
        assert ids == list(range(103))

    def test_iter_batches_exact_sizes(self, ray_start_local):
        batches = list(
            rd.range(70, parallelism=3).iter_batches(batch_size=32)
        )
        assert [len(b["id"]) for b in batches] == [32, 32, 6]
        batches = list(
            rd.range(70, parallelism=3).iter_batches(batch_size=32, drop_last=True)
        )
        assert [len(b["id"]) for b in batches] == [32, 32]

    def test_iter_batches_to_device(self, ray_start_local):
        import jax

        dev = jax.devices("cpu")[0]
        batches = list(
            rd.range(16, parallelism=2).iter_batches(batch_size=8, device=dev)
        )
        assert len(batches) == 2
        assert isinstance(batches[0]["id"], jax.Array)
        assert batches[0]["id"].sum() == sum(range(8))


class TestFileIO:
    def test_parquet_roundtrip(self, ray_start_local, tmp_path):
        pa = pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        for i in range(3):
            t = pa.table({"x": list(range(i * 10, i * 10 + 10)),
                          "y": [float(v) for v in range(10)]})
            pq.write_table(t, str(tmp_path / f"part-{i}.parquet"))
        ds = rd.read_parquet(str(tmp_path))
        assert ds.count() == 30
        assert ds.schema()["x"] == "int64"
        assert sorted(r["x"] for r in ds.take_all()) == list(range(30))

    def test_csv(self, ray_start_local, tmp_path):
        pytest.importorskip("pyarrow")
        p = tmp_path / "data.csv"
        p.write_text("a,b\n1,x\n2,y\n3,z\n")
        ds = rd.read_csv(str(p))
        assert ds.count() == 3
        assert ds.take(1)[0]["a"] == 1


class TestTrainIntegration:
    def test_trainer_feeds_from_dataset(self, ray_start_regular):
        """JaxTrainer ingests a Dataset via get_dataset_shard → iter_batches
        (VERDICT round-2 item 4: train from a Dataset, not synthetic_batch)."""
        from ray_tpu.train import JaxTrainer, ScalingConfig, get_dataset_shard, report

        ds = rd.range(64, parallelism=4).map_batches(
            lambda b: {"x": b["id"].astype(np.float32),
                       "y": (b["id"] * 3 + 1).astype(np.float32)}
        )

        def train_loop(config):
            import jax
            import jax.numpy as jnp

            shard = get_dataset_shard("train")
            w = jnp.zeros(2)  # fit y = a*x + b
            seen = 0
            for _ in range(3):  # epochs
                for batch in shard.iter_batches(batch_size=8):
                    x, y = jnp.asarray(batch["x"]), jnp.asarray(batch["y"])
                    seen += int(x.shape[0])

                    def loss(w):
                        return jnp.mean((w[0] * x + w[1] - y) ** 2)

                    w = w - 0.01 * jax.grad(loss)(w)
            report({"rows_seen": seen, "final_loss": float(
                jnp.mean((w[0] * jnp.asarray(batch["x"]) + w[1]
                          - jnp.asarray(batch["y"])) ** 2))})

        trainer = JaxTrainer(
            train_loop,
            scaling_config=ScalingConfig(num_workers=2, use_tpu=False),
            datasets={"train": ds},
        )
        result = trainer.fit()
        assert result.error is None
        # each of 2 workers saw its 32-row shard 3 times
        assert result.metrics["rows_seen"] == 96
        all_ranks = result.metrics["_all_ranks"]
        assert set(all_ranks) == {0, 1}
        assert all(m["rows_seen"] == 96 for m in all_ranks.values())


def test_flat_map_union_repartition(ray_start_local):
    rdata = rd
    ds = rdata.from_items([1, 2, 3]).flat_map(lambda r: [int(r)] * int(r))
    assert sorted(int(r) for r in ds.take_all()) == [1, 2, 2, 3, 3, 3]

    a = rdata.from_items([1, 2])
    b = rdata.from_items([3, 4])
    assert sorted(int(r) for r in a.union(b).take_all()) == [1, 2, 3, 4]

    rp = rdata.range(10, parallelism=5).repartition(2)
    refs = list(rp.iter_block_refs())
    assert len(refs) == 2
    assert sorted(r["id"] for r in rp.take_all()) == list(range(10))


def test_sort_and_groupby(ray_start_local):
    rdata = rd
    items = [{"k": i % 3, "v": float(i)} for i in range(12)]
    ds = rdata.from_items(items)

    s = ds.sort("v", descending=True).take_all()
    assert [r["v"] for r in s] == sorted((float(i) for i in range(12)),
                                         reverse=True)

    g = ds.groupby("k")
    assert g.count() == {0: 4, 1: 4, 2: 4}
    assert g.sum("v") == {0: 0 + 3 + 6 + 9, 1: 1 + 4 + 7 + 10, 2: 2 + 5 + 8 + 11}
    assert g.mean("v")[0] == (0 + 3 + 6 + 9) / 4
    assert g.min("v") == {0: 0.0, 1: 1.0, 2: 2.0}
    assert g.max("v") == {0: 9.0, 1: 10.0, 2: 11.0}


def test_transforms_chain_after_materialized_ops(ray_start_local):
    # regression: map after union/sort must not silently drop the data
    a = rd.from_items([3, 1])
    b = rd.from_items([2, 4])
    u = a.union(b).map(lambda r: int(r) * 10)
    assert sorted(int(r) for r in u.take_all()) == [10, 20, 30, 40]

    s = rd.from_items([{"k": "b"}, {"k": "a"}]).sort("k")
    assert [r["k"] for r in s.take_all()] == ["a", "b"]
    assert s.limit(1).take_all()[0]["k"] == "a"


def test_distributed_shuffle_sort(ray_start_regular):
    """Range-partitioned shuffle sort (data/shuffle.py ↔ reference
    push_based_shuffle.py): output stays MULTI-block (never concatenated on
    the driver), globally ordered across block boundaries."""
    import numpy as np

    rng = np.random.default_rng(7)
    vals = rng.permutation(500).astype(np.int64)
    ds = rd.from_items([{"v": int(v)} for v in vals], parallelism=8)
    out = ds.sort("v", num_partitions=4)
    refs = list(out.iter_block_refs())
    assert len(refs) == 4  # partitioned output, not one driver-side concat
    got = [int(r["v"]) for r in out.take_all()]
    assert got == sorted(range(500))

    # descending too
    got_d = [int(r["v"]) for r in ds.sort("v", descending=True).take_all()]
    assert got_d == sorted(range(500), reverse=True)


def test_distributed_random_shuffle_global(ray_start_regular):
    """random_shuffle is a GLOBAL shuffle: rows cross block boundaries, the
    multiset is preserved, and the seed makes it deterministic."""
    ds = rd.range(200, parallelism=4)
    out = ds.random_shuffle(seed=3)
    rows = [int(r["id"]) for r in out.take_all()]
    assert sorted(rows) == list(range(200))
    assert rows != list(range(200))  # actually shuffled
    # global: the first output partition must contain rows from >1 input
    # block (input blocks are contiguous ranges of 50)
    first_block = __import__("ray_tpu").get(next(iter(out.iter_block_refs())))
    first = [int(v) for v in first_block["id"]]
    assert len({v // 50 for v in first}) > 1, first
    # determinism
    again = [int(r["id"]) for r in ds.random_shuffle(seed=3).take_all()]
    assert rows == again


def test_groupby_map_groups_shuffled(ray_start_regular):
    """map_groups rides the hash shuffle: every key's rows meet in one task."""
    items = [{"k": i % 5, "v": float(i)} for i in range(100)]
    ds = rd.from_items(items, parallelism=8)

    def spread(group):
        vs = np.asarray(group["v"])
        return {"k": group["k"][:1], "spread": np.asarray([vs.max() - vs.min()])}

    out = ds.groupby("k").map_groups(spread, num_partitions=3)
    rows = {int(r["k"]): float(r["spread"]) for r in out.take_all()}
    assert rows == {k: 95.0 for k in range(5)}


def test_groupby_string_keys_cross_process(ray_start_regular):
    """String keys must route to the SAME partition from every map task.

    Map tasks run in separate worker processes whose builtins.hash salts
    differ (PYTHONHASHSEED is unset) — a per-process hash would scatter one
    key across partitions and map_groups would emit duplicated groups.
    The partitioner therefore uses a process-independent hash (crc32)."""
    keys = ["alpha", "beta", "gamma", "delta", "epsilon"]
    items = [{"k": keys[i % 5], "v": float(i)} for i in range(200)]
    # many blocks => many distinct map worker processes
    ds = rd.from_items(items, parallelism=8)

    def count(group):
        return {"k": group["k"][:1],
                "n": np.asarray([len(np.asarray(group["v"]))])}

    out = ds.groupby("k").map_groups(count, num_partitions=4)
    rows = [(str(r["k"]), int(r["n"])) for r in out.take_all()]
    seen = {}
    for k, n in rows:
        assert k not in seen, f"key {k!r} split across partitions: {rows}"
        seen[k] = n
    assert seen == {k: 40 for k in keys}


def test_preprocessors(ray_start_local):
    """fit/transform layer (parity: ray/data/preprocessors/)."""
    from ray_tpu.data.preprocessors import (
        BatchMapper,
        Chain,
        LabelEncoder,
        MinMaxScaler,
        StandardScaler,
    )

    rows = [{"x": float(i), "y": float(i % 4), "label": ["a", "b", "c"][i % 3]}
            for i in range(64)]
    ds = rd.from_items(rows, parallelism=4)

    sc = StandardScaler(["x"]).fit(ds)
    out = np.concatenate([b["x"] for b in [
        __import__("ray_tpu").get(r) for r in sc.transform(ds).iter_block_refs()
    ]])
    assert abs(out.mean()) < 1e-6 and abs(out.std() - 1.0) < 1e-2

    mm = MinMaxScaler(["y"]).fit(ds)
    vals = [r["y"] for r in mm.transform(ds).take_all()]
    assert min(vals) == 0.0 and max(vals) == 1.0

    le = LabelEncoder("label").fit(ds)
    codes = {r["label"] for r in le.transform(ds).take_all()}
    assert codes == {0, 1, 2}
    assert list(le.classes_) == ["a", "b", "c"]

    chained = Chain(
        StandardScaler(["x"]),
        BatchMapper(lambda b: {**b, "x": np.asarray(b["x"]) * 2.0}),
    ).fit_transform(ds)
    xs = np.asarray([r["x"] for r in chained.take_all()])
    assert abs(xs.std() - 2.0) < 2e-2

    with pytest.raises(RuntimeError, match="must be fit"):
        StandardScaler(["x"]).transform(ds)


def test_dataset_stats(ray_start_local):
    """Per-op execution stats (parity: Dataset.stats / _internal/stats.py)."""
    ds = rd.range(100, parallelism=4).map_batches(lambda b: b)
    assert "not been executed" in ds.stats()
    _ = ds.take_all()
    s = ds.stats()
    assert "Read" in s and "MapBatches" in s
    assert "blocks=4" in s


def test_read_json_from_pandas_write_parquet(ray_start_local, tmp_path):
    pd = pytest.importorskip("pandas")
    pytest.importorskip("pyarrow")
    import json as _json

    # read_json (jsonl)
    p = tmp_path / "rows.jsonl"
    p.write_text("\n".join(_json.dumps({"a": i, "b": f"s{i}"})
                           for i in builtins_range(6)))
    ds = rd.read_json(str(p))
    assert ds.count() == 6
    assert sorted(r["a"] for r in ds.take_all()) == list(builtins_range(6))

    # from_pandas
    df = pd.DataFrame({"x": [1, 2, 3], "y": [1.0, 2.0, 3.0]})
    ds2 = rd.from_pandas(df)
    assert ds2.count() == 3 and ds2.take(1)[0]["y"] == 1.0

    # write_parquet roundtrip
    outdir = tmp_path / "out"
    files = rd.range(40, parallelism=3).write_parquet(str(outdir))
    assert len(files) == 3
    back = rd.read_parquet(str(outdir))
    assert sorted(r["id"] for r in back.take_all()) == list(builtins_range(40))


def test_actor_pool_stage_does_not_clobber_executor_cap(ray_start_local):
    """An actor-pool stage's in-flight cap is a PER-STAGE _bounded
    parameter: while its lazy stream drains, a concurrently-pulled
    task-based stage still sees the executor-wide max_in_flight (the old
    save/restore around the generator leaked the pool's cap to every
    other stage for the stage's whole lifetime)."""
    from ray_tpu.data.executor import (
        ActorPoolStrategy,
        MapBatchesOp,
        ReadOp,
        StreamingExecutor,
    )

    ex = StreamingExecutor(max_tasks_in_flight=8)
    ops = [
        ReadOp([(lambda i=i: {"id": np.array([i])}) for i in range(6)]),
        MapBatchesOp(
            fn=lambda b: {"id": b["id"] + 100},
            compute=ActorPoolStrategy(
                size=1, max_tasks_in_flight_per_actor=1
            ),
        ),
        MapBatchesOp(fn=lambda b: {"id": b["id"] * 2}),
    ]
    caps_seen = []
    stream = ex.execute(ops)
    import ray_tpu

    out = []
    for ref in stream:
        # mid-drain: the executor-wide cap must be untouched by the pool
        caps_seen.append(ex.max_in_flight)
        out.append(int(ray_tpu.get(ref)["id"][0]))
    assert sorted(out) == [(i + 100) * 2 for i in range(6)]
    assert set(caps_seen) == {8}, caps_seen
