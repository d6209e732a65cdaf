"""Data library: all-to-all sort/groupby, file sinks, jax batch feed
(reference: `data/_internal/planner/exchange/`, `data/grouped_data.py`,
`data/iterator.py:258` iter_torch_batches)."""

import json
import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rdata


def test_distributed_sort(ray_start_regular):
    rng = np.random.RandomState(0)
    vals = rng.permutation(2000)
    ds = rdata.from_numpy(vals, column="x").repartition(8).sort("x")
    out = [r["x"] for r in ds.take_all()]
    assert out == sorted(vals.tolist())

    desc = rdata.from_numpy(vals, column="x").repartition(4).sort(
        "x", descending=True)
    out = [r["x"] for r in desc.take_all()]
    assert out == sorted(vals.tolist(), reverse=True)


def test_groupby_aggregations(ray_start_regular):
    rows = [{"k": i % 3, "v": float(i)} for i in range(30)]
    ds = rdata.from_items(rows).repartition(5)

    sums = {r["k"]: r["v_sum"] for r in ds.groupby("k").sum("v").take_all()}
    expect = {}
    for r in rows:
        expect[r["k"]] = expect.get(r["k"], 0.0) + r["v"]
    assert sums == expect

    counts = {r["k"]: r["k_count"]
              for r in ds.groupby("k").count().take_all()}
    assert counts == {0: 10, 1: 10, 2: 10}

    means = {r["k"]: r["v_mean"]
             for r in ds.groupby("k").mean("v").take_all()}
    assert means[0] == pytest.approx(expect[0] / 10)


def test_write_and_read_roundtrip(ray_start_regular, tmp_path):
    rows = [{"a": i, "b": f"s{i}"} for i in range(100)]
    ds = rdata.from_items(rows).repartition(4)

    pq_dir = str(tmp_path / "pq")
    files = ds.write_parquet(pq_dir)
    assert files and all(f.endswith(".parquet") for f in files)
    back = rdata.read_parquet(pq_dir)
    assert sorted(r["a"] for r in back.take_all()) == list(range(100))

    js_dir = str(tmp_path / "js")
    ds.write_json(js_dir)
    back = rdata.read_json(js_dir)
    assert sorted(r["a"] for r in back.take_all()) == list(range(100))

    csv_dir = str(tmp_path / "csv")
    ds.write_csv(csv_dir)
    back = rdata.read_csv(csv_dir)
    assert sorted(r["a"] for r in back.take_all()) == list(range(100))


def test_iter_jax_batches(ray_start_regular):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    ds = rdata.from_numpy(np.arange(64, dtype=np.float32), column="x")
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    seen = 0
    for batch in ds.iterator().iter_jax_batches(
            batch_size=16, sharding=sharding):
        assert isinstance(batch["x"], jax.Array)
        assert batch["x"].sharding == sharding
        seen += int(batch["x"].shape[0])
    assert seen == 64


def test_union_and_zip(ray_start_regular):
    """Multi-input plans: union concatenates streams; zip merges columns
    row-aligned with _1 suffix on collisions (reference: Dataset.union,
    Dataset.zip)."""
    left = rdata.range(4).map(lambda r: {"id": r["id"], "x": r["id"] * 10})
    right = rdata.range(4).map(lambda r: {"id": r["id"] + 100,
                                          "y": r["id"]})

    u = left.union(right)
    assert u.count() == 8
    ids = [r["id"] for r in u.take_all()]
    assert ids[:4] == [0, 1, 2, 3] and set(ids[4:]) == {100, 101, 102, 103}

    z = left.zip(right)
    rows = z.take_all()
    assert len(rows) == 4
    assert rows[1] == {"id": 1, "x": 10, "id_1": 101, "y": 1}

    # Downstream ops compose after the multi-input stage.
    assert left.union(right).filter(
        lambda r: r["id"] >= 100).count() == 4

    # Length mismatch is an error, not silent truncation.
    with pytest.raises(Exception, match="zip"):
        rdata.range(3).zip(rdata.range(5)).take_all()


def test_row_ops_honor_resource_options(ray_start_regular):
    """map/filter/flat_map honor concurrency/num_cpus by routing through
    the distributed map_batches machinery, and RAISE on unknown kwargs —
    the old **_ignored silently ran serial."""
    import os

    out = rdata.range(16, override_num_blocks=4).map(
        lambda r: {"v": r["id"] * 2, "pid": os.getpid()},
        concurrency=2).take_all()
    assert sorted(r["v"] for r in out) == [i * 2 for i in range(16)]
    # Ran in worker processes, not the driver.
    assert all(r["pid"] != os.getpid() for r in out)

    assert rdata.range(16).filter(
        lambda r: r["id"] < 4, num_cpus=0.5).count() == 4
    assert rdata.range(4).flat_map(
        lambda r: [r, r], concurrency=2).count() == 8

    with pytest.raises(TypeError, match="bogus"):
        rdata.range(4).map(lambda r: r, bogus=1)
    with pytest.raises(TypeError, match="unsupported"):
        rdata.range(4).filter(lambda r: True, scheduling_strategy="SPREAD")
