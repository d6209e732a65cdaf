"""Offline experience I/O — JSONL episode logs.

Reference: `rllib/offline/json_writer.py` / `json_reader.py` (newline-
delimited JSON sample batches, sharded files, env-rollout recording via
`config.offline_data(output=...)`).  Rows here are per-transition with an
`eps_id`, so readers can reassemble episodes and compute return-to-go for
advantage-weighted algorithms (MARWIL) without the writer knowing gamma.
"""

from __future__ import annotations

import glob as _glob
import json
import os
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np


class JsonWriter:
    """Append transitions as JSONL; rolls to a new shard every
    `max_rows_per_file` rows (reference: JsonWriter sharding)."""

    def __init__(self, path: str, max_rows_per_file: int = 100_000):
        import uuid

        self._dir = path
        os.makedirs(path, exist_ok=True)
        self._max = max_rows_per_file
        self._rows_in_file = 0
        self._shard = 0
        # Unique per-writer token (reference JsonWriter does the same):
        # two recordings into one directory must neither append to each
        # other's shards nor collide eps_ids at read time.
        self._token = uuid.uuid4().hex[:8]
        self._fh = None

    def _roll(self) -> None:
        if self._fh is not None:
            self._fh.close()
        fname = os.path.join(
            self._dir, f"rollouts-{self._token}-{self._shard:05d}.jsonl")
        self._fh = open(fname, "w")
        self._shard += 1
        self._rows_in_file = 0

    def write(self, row: Dict[str, Any]) -> None:
        if self._fh is None or self._rows_in_file >= self._max:
            self._roll()
        self._fh.write(json.dumps(
            {k: (v.tolist() if isinstance(v, np.ndarray) else
                 v.item() if isinstance(v, np.generic) else v)
             for k, v in row.items()}) + "\n")
        self._rows_in_file += 1

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class JsonReader:
    """Reads a JSONL rollout directory (or single file) back into rows;
    `with_returns(gamma)` appends discounted return-to-go per transition
    (grouped by eps_id, episode order = row order within a shard)."""

    def __init__(self, path: str):
        if os.path.isdir(path):
            self._files = sorted(_glob.glob(os.path.join(path, "*.jsonl")))
        else:
            self._files = [path]
        if not self._files:
            raise FileNotFoundError(f"no .jsonl files under {path}")

    def rows(self) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        for f in self._files:
            with open(f) as fh:
                for ln in fh:
                    ln = ln.strip()
                    if ln:
                        out.append(json.loads(ln))
        return out

    def with_returns(self, gamma: float = 0.99) -> List[Dict[str, Any]]:
        return compute_returns(self.rows(), gamma)

    def to_dataset(self):
        """Rows as a ray_tpu.data Dataset (requires a live cluster)."""
        from ray_tpu import data as rdata

        return rdata.from_items(self.rows())


class ParquetWriter:
    """Append transitions, flushed as parquet shards — the interchange
    format with `ray_tpu.data` (reference: `rllib/offline/` reads sample
    batches through Ray Data; JSONL is the legacy path)."""

    def __init__(self, path: str, max_rows_per_file: int = 100_000):
        import uuid

        self._dir = path
        os.makedirs(path, exist_ok=True)
        self._max = max_rows_per_file
        self._rows: List[Dict[str, Any]] = []
        self._shard = 0
        self._token = uuid.uuid4().hex[:8]

    def write(self, row: Dict[str, Any]) -> None:
        self._rows.append(
            {k: (v.tolist() if isinstance(v, np.ndarray) else
                 v.item() if isinstance(v, np.generic) else v)
             for k, v in row.items()})
        if len(self._rows) >= self._max:
            self._flush()

    def _flush(self) -> None:
        if not self._rows:
            return
        import pyarrow as pa
        import pyarrow.parquet as pq

        cols: Dict[str, list] = {}
        for r in self._rows:
            for k in r:
                cols.setdefault(k, [])
        for r in self._rows:
            for k in cols:
                cols[k].append(r.get(k))
        pq.write_table(pa.table(cols), os.path.join(
            self._dir, f"rollouts-{self._token}-{self._shard:05d}.parquet"))
        self._shard += 1
        self._rows = []

    def close(self) -> None:
        self._flush()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class DatasetReader:
    """Stream transition batches out of a `ray_tpu.data.Dataset` —
    offline training ingests Data pipelines (parquet shards, any Data
    source) directly instead of JSONL-only (reference: `rllib/offline/`
    new-stack readers are Ray Data datasets).

    `path_or_dataset`: a Dataset, or a path read via
    `data.read_parquet`. `batches(batch_size)` yields numpy dicts with
    float32 obs/rewards, ready for a Learner; `rows()` materializes (for
    small datasets / return computation).
    """

    def __init__(self, path_or_dataset):
        from ray_tpu import data as rdata

        if isinstance(path_or_dataset, str):
            self._ds = rdata.read_parquet(path_or_dataset)
        else:
            self._ds = path_or_dataset

    @property
    def dataset(self):
        return self._ds

    def rows(self) -> List[Dict[str, Any]]:
        return self._ds.take_all()

    def with_returns(self, gamma: float = 0.99) -> List[Dict[str, Any]]:
        return compute_returns(self.rows(), gamma)

    def batches(self, batch_size: int,
                epochs: Optional[int] = None) -> Iterator[Dict[str, Any]]:
        """Epoch-looped numpy batches (None = loop forever)."""
        epoch = 0
        while epochs is None or epoch < epochs:
            for b in self._ds.iter_batches(batch_size=batch_size,
                                           batch_format="numpy",
                                           drop_last=True):
                yield {k: (np.stack([np.asarray(x, np.float32)
                                     for x in v])
                           if v.dtype == object else v)
                       for k, v in b.items()}
            epoch += 1


def compute_returns(rows: List[Dict[str, Any]],
                    gamma: float = 0.99) -> List[Dict[str, Any]]:
    """Append discounted return-to-go per transition, grouping by eps_id
    in row order (shared by JsonReader.with_returns and MARWIL's
    in-memory ingestion).  Rows must carry 'rewards' (or a precomputed
    'returns', which is left untouched)."""
    by_ep: Dict[Any, List[int]] = {}
    for i, r in enumerate(rows):
        if "returns" not in r and "rewards" not in r:
            raise ValueError(
                f"offline row {i} has neither 'rewards' nor a precomputed "
                f"'returns' column (keys: {sorted(r)})")
        by_ep.setdefault(r.get("eps_id", 0), []).append(i)
    for idxs in by_ep.values():
        ret = 0.0
        for i in reversed(idxs):
            if "returns" in rows[i]:
                ret = float(rows[i]["returns"])
                continue
            ret = float(rows[i]["rewards"]) + gamma * ret
            rows[i]["returns"] = ret
    return rows


def record_rollouts(env_spec, path: str, num_episodes: int,
                    policy: Optional[Callable[[np.ndarray], Any]] = None,
                    seed: int = 0,
                    output_format: str = "json") -> Dict[str, Any]:
    """Roll `num_episodes` episodes of `env_spec` and persist them
    (reference: `rllib/offline/` output API + `rllib train ... --out`).
    `policy(obs) -> action`; None = uniform random.
    `output_format`: "json" (JSONL shards) or "parquet" (Data-ready)."""
    import uuid

    from ray_tpu.rllib.env.cartpole import make_env

    env = make_env(env_spec, seed=seed)
    rng = np.random.RandomState(seed)
    returns: List[float] = []
    # Globally-unique episode ids: a second recording into the same
    # directory must not merge its episodes with this run's at read time.
    run = uuid.uuid4().hex[:8]
    writer_cls = ParquetWriter if output_format == "parquet" else JsonWriter
    with writer_cls(path) as w:
        for ep in range(num_episodes):
            obs, _ = env.reset(seed=seed * 100003 + ep)
            done, total, t = False, 0.0, 0
            while not done:
                if policy is None:
                    act = env.action_space.sample(rng)
                else:
                    act = policy(obs)
                nxt, r, term, trunc, _ = env.step(act)
                w.write({"eps_id": f"{run}-{ep}", "t": t, "obs": obs,
                         "actions": act, "rewards": r, "next_obs": nxt,
                         "terminateds": term, "truncateds": trunc})
                obs, total, t = nxt, total + r, t + 1
                done = term or trunc
            returns.append(total)
    return {"num_episodes": num_episodes,
            "episode_return_mean": float(np.mean(returns))}
