"""Concurrent operator execution + new training-ingest sources.

Covers an early review finding: stage-2 tasks running while stage-1 still
produces (concurrent scheduler), per-op budgets/backpressure plumbing,
and TFRecord / WebDataset ingest."""

import os
import struct
import time

import pytest


# ------------------------------------------------------------ pipelining

def test_stage2_runs_while_stage1_producing(ray_start_regular, tmp_path):
    """With a slow source and an actor-map stage, the first map
    completion must land BEFORE the last source task finishes — i.e. the
    stages genuinely overlap (reference: streaming_executor.py operator
    loop)."""
    import ray_tpu
    from ray_tpu import data as rdata
    from ray_tpu.data.datasource import Datasource

    log = tmp_path / "events.log"

    class SlowSource(Datasource):
        """Tasks 0..n-2 produce after a short sleep; the LAST task
        refuses to finish until the log proves a map already ran. A
        serialized executor (maps gated on all sources) deadlocks here
        and hits the 45s timeout marker; a pipelined one sails through."""

        def __init__(self, n_tasks, log_path):
            self._n = n_tasks
            self._log = str(log_path)

        def get_read_tasks(self, parallelism):
            tasks = []
            for i in range(self._n):
                def make(i=i, log=self._log, last=(i == self._n - 1)):
                    def read():
                        import os as _os
                        import time as _t

                        from ray_tpu.data.block import BlockAccessor

                        if last:
                            deadline = _t.monotonic() + 45
                            while _t.monotonic() < deadline:
                                if (_os.path.exists(log) and any(
                                        ln.startswith("M")
                                        for ln in open(log))):
                                    with open(log, "a") as f:
                                        f.write("GATED-OK\n")
                                    break
                                _t.sleep(0.2)
                            else:
                                with open(log, "a") as f:
                                    f.write("GATED-TIMEOUT\n")
                        else:
                            _t.sleep(0.5)
                        with open(log, "a") as f:
                            f.write(f"S{i} {_t.monotonic()}\n")
                        yield BlockAccessor.from_rows(
                            [{"v": i * 10 + j} for j in range(4)])
                    return read
                tasks.append(make(i))
            return tasks

    logp = str(log)

    def mark(batch):
        with open(logp, "a") as f:
            f.write(f"M {time.monotonic()}\n")
        batch["v"] = batch["v"] * 2
        return batch

    class Marker:
        def __call__(self, batch):
            return mark(batch)

    ds = rdata.read_datasource(SlowSource(6, log)).map_batches(
        Marker, concurrency=2)
    rows = ds.take_all()
    assert sorted(r["v"] for r in rows) == sorted(
        (i * 10 + j) * 2 for i in range(6) for j in range(4))

    text = log.read_text()
    # Causal overlap proof: the last source task observed a completed
    # map while it was still running.
    assert "GATED-OK" in text, (
        "map stage only ran after ALL source tasks finished — "
        "stages are serialized, not pipelined:\n" + text)


def test_concurrent_executor_budget_and_policies(ray_start_regular):
    """Budget slots derive from cluster CPUs; chains complete correctly
    through the concurrent scheduler."""
    from ray_tpu import data as rdata
    from ray_tpu.data._internal.concurrent_executor import (
        ConcurrentExecutor,
    )

    slots = ConcurrentExecutor.budgets(2)
    assert slots >= 2

    ds = rdata.range(64, override_num_blocks=8).map_batches(
        lambda b: {"id": b["id"] + 1}).map_batches(
        _Plus2, concurrency=2)
    assert sorted(r["id"] for r in ds.take_all()) == list(range(3, 67))


def test_tiny_output_buffer_with_straggler_no_deadlock(ray_start_regular):
    """Regression: a straggling FIRST source task parks many later
    sequence numbers in the final reorder buffer. With a tiny
    OutputBufferPolicy cap this must still complete — the final op is
    exempt from the output-buffer count, else the straggler's own map
    task could never launch (permanent deadlock + busy spin)."""
    from ray_tpu.data._internal import plan as plan_mod
    from ray_tpu.data._internal.concurrent_executor import (
        ConcurrencyCapPolicy, OutputBufferPolicy, build_pipeline,
    )
    from ray_tpu.data.datasource import Datasource
    from ray_tpu.data.block import BlockAccessor

    class StragglerFirst(Datasource):
        def get_read_tasks(self, parallelism):
            tasks = []
            for i in range(20):
                def make(i=i):
                    def read():
                        import time as _t

                        if i == 0:
                            _t.sleep(2.5)  # every other task beats it
                        yield BlockAccessor.from_rows([{"v": i}])
                    return read
                tasks.append(make(i))
            return tasks

    pipe = build_pipeline(
        plan_mod.Read(StragglerFirst(), -1), None,
        [[plan_mod.MapBatches(lambda b: {"v": b["v"] * 3},
                              batch_size=None, batch_format="numpy")]],
        policies=(ConcurrencyCapPolicy(), OutputBufferPolicy(2)))
    assert pipe is not None
    import time as _t

    t0 = _t.monotonic()
    blocks = list(pipe.stream())
    assert _t.monotonic() - t0 < 60
    vals = sorted(int(r["v"]) for b in blocks
                  for r in BlockAccessor(b).rows())
    assert vals == [i * 3 for i in range(20)]


class _Plus2:
    def __call__(self, batch):
        batch["id"] = batch["id"] + 2
        return batch


# ------------------------------------------------------------- tfrecords

def _varint(n: int) -> bytes:
    out = b""
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _ld(field: int, payload: bytes) -> bytes:
    return _varint((field << 3) | 2) + _varint(len(payload)) + payload


def _tf_example(features: dict) -> bytes:
    body = b""
    for key, value in features.items():
        if isinstance(value, bytes):
            flist = _ld(1, _ld(1, value))                  # bytes_list
        elif isinstance(value, float):
            flist = _ld(2, _ld(1, struct.pack("<f", value)))  # float_list
        else:
            flist = _ld(3, _ld(1, _varint(int(value))))    # int64_list
        entry = _ld(1, key.encode()) + _ld(2, flist)
        body += _ld(1, entry)
    return _ld(1, body)  # Example.features


def _write_tfrecord(path, examples):
    with open(path, "wb") as f:
        for ex in examples:
            payload = _tf_example(ex)
            f.write(struct.pack("<Q", len(payload)))
            f.write(b"\x00\x00\x00\x00")  # length crc (unverified)
            f.write(payload)
            f.write(b"\x00\x00\x00\x00")  # data crc


def test_read_tfrecords(ray_start_regular, tmp_path):
    from ray_tpu import data as rdata

    path = tmp_path / "data.tfrecord"
    _write_tfrecord(path, [
        {"label": 3, "text": b"hello", "weight": 1.5},
        {"label": 7, "text": b"world", "weight": 2.5},
    ])
    rows = rdata.read_tfrecords(str(path)).take_all()
    assert len(rows) == 2
    by_label = {r["label"]: r for r in rows}
    assert by_label[3]["text"] == b"hello"
    assert by_label[7]["weight"] == pytest.approx(2.5)


def test_read_tfrecords_list_features(ray_start_regular, tmp_path):
    """Multi-value feature lists survive as lists; packed int64 lists
    decode."""
    from ray_tpu import data as rdata

    # int64_list with three packed varints.
    flist = _ld(3, _ld(1, _varint(1) + _varint(200) + _varint(300000)))
    entry = _ld(1, b"ids") + _ld(2, flist)
    payload = _ld(1, _ld(1, entry))
    path = tmp_path / "lists.tfrecord"
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(payload)) + b"\0\0\0\0"
                + payload + b"\0\0\0\0")
    rows = rdata.read_tfrecords(str(path)).take_all()
    assert list(rows[0]["ids"]) == [1, 200, 300000]


# ------------------------------------------------------------- webdataset

def test_read_webdataset(ray_start_regular, tmp_path):
    import io
    import json
    import tarfile

    from ray_tpu import data as rdata

    shard = tmp_path / "shard-000000.tar"
    with tarfile.open(shard, "w") as tar:
        def add(name, data: bytes):
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))

        add("sample_a.jpg", b"\xff\xd8JPGDATA")
        add("sample_a.cls", b"3")
        add("sample_a.json", json.dumps({"caption": "a cat"}).encode())
        add("sample_b.jpg", b"\xff\xd8OTHER")
        add("sample_b.cls", b"7")

    rows = rdata.read_webdataset(str(shard)).take_all()
    assert len(rows) == 2
    by_key = {r["__key__"]: r for r in rows}
    assert by_key["sample_a"]["cls"] == 3
    assert by_key["sample_a"]["jpg"] == b"\xff\xd8JPGDATA"
    assert by_key["sample_a"]["json"]["caption"] == "a cat"
    assert by_key["sample_b"]["cls"] == 7
