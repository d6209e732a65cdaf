"""perf.py — single-node microbenchmarks, named after the reference's
`python/ray/_private/ray_perf.py` metrics so the rows compare directly
(SCALE.md publishes the table; the envelope harness `scale_bench.py`
covers the 10^4..10^6 end).

Each benchmark runs for a fixed wall budget and reports ops/s; the
process count is tiny (one cluster, a couple of workers) so the numbers
are per-core-meaningful even on a 1-vCPU host.

Usage: python scripts/perf.py [--seconds-per-bench 5]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import ray_tpu  # noqa: E402


def timed(fn, budget_s: float, batch: int = 1):
    """-> ops/s over ~budget_s of repeated fn() calls (fn does `batch`
    operations per call)."""
    # Warmup.
    fn()
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < budget_s:
        fn()
        n += batch
    return n / (time.perf_counter() - t0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds-per-bench", type=float, default=5.0)
    args = ap.parse_args()
    budget = args.seconds_per_bench

    ray_tpu.init(num_cpus=4, num_tpus=0,
                 object_store_memory=512 * 1024 * 1024)
    results = {}

    @ray_tpu.remote
    def nop():
        return b"ok"

    @ray_tpu.remote
    class Actor:
        def nop(self):
            return b"ok"

    # --- puts / gets (reference rows: "single client put calls",
    # "single client get calls") --------------------------------------
    small = b"x" * 1024
    results["single_client_put_calls_per_s"] = timed(
        lambda: ray_tpu.put(small), budget)
    ref = ray_tpu.put(small)
    results["single_client_get_calls_per_s"] = timed(
        lambda: ray_tpu.get(ref, timeout=30), budget)

    big = b"x" * (1024 * 1024)
    results["single_client_put_gigabytes_per_s"] = timed(
        lambda: ray_tpu.put(big), budget) / 1024.0
    bref = ray_tpu.put(big)
    results["single_client_get_gigabytes_per_s"] = timed(
        lambda: ray_tpu.get(bref, timeout=30), budget) / 1024.0

    # --- tasks (reference rows: "single client tasks sync/async") ----
    results["single_client_tasks_sync_per_s"] = timed(
        lambda: ray_tpu.get(nop.remote(), timeout=30), budget)

    def tasks_async():
        ray_tpu.get([nop.remote() for _ in range(100)], timeout=60)

    results["single_client_tasks_async_per_s"] = timed(
        tasks_async, budget, batch=100)

    # --- actor calls (reference rows: "actor calls sync/async") ------
    actor = Actor.remote()
    ray_tpu.get(actor.nop.remote(), timeout=60)
    results["single_client_actor_calls_sync_per_s"] = timed(
        lambda: ray_tpu.get(actor.nop.remote(), timeout=30), budget)

    def actor_async():
        ray_tpu.get([actor.nop.remote() for _ in range(100)], timeout=60)

    results["single_client_actor_calls_async_per_s"] = timed(
        actor_async, budget, batch=100)

    # --- wait (reference row: "single client wait 1k refs") ----------
    refs1k = [ray_tpu.put(small) for _ in range(1000)]
    results["single_client_wait_1k_refs_per_s"] = timed(
        lambda: ray_tpu.wait(refs1k, num_returns=1000, timeout=60),
        budget)

    ray_tpu.shutdown()

    sys.stderr.write(
        f"{'metric':<45}{'ops/s':>12}\n" + "-" * 57 + "\n")
    for k, v in results.items():
        sys.stderr.write(f"{k:<45}{v:>12.1f}\n")
    print(json.dumps({k: round(v, 2) for k, v in results.items()}))


if __name__ == "__main__":
    main()
