"""Control-plane scalability envelope (reference harness:
`release/benchmarks/README.md:5-31`, `python/ray/_private/ray_perf.py`).

Runs an in-process multi-raylet cluster through the envelope BASELINE.md
targets — many submitted tasks, hundreds of actors, placement groups,
a large broadcast — and prints a JSON summary + a markdown table for
SCALE.md. Sized by flags so the same harness runs as a quick smoke or a
full soak.

Usage:
    python scripts/scale_bench.py [--raylets 8] [--tasks 10000]
        [--actors 500] [--pgs 100] [--broadcast-mb 100] [--queued 100000]
        [--object-args 10000] [--store-object-kb 128] [--returns 3000]

--object-args / --returns / --queued take 0 to disable their phases;
--store-object-kb sizes the phase-6 payloads (default 128 KiB, above
the 100 KiB inline threshold so objects are store-backed).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--raylets", type=int, default=8)
    ap.add_argument("--cpus-per-raylet", type=int, default=2)
    ap.add_argument("--tasks", type=int, default=10000)
    ap.add_argument("--actors", type=int, default=500)
    ap.add_argument("--actor-calls", type=int, default=5000)
    ap.add_argument("--pgs", type=int, default=100)
    ap.add_argument("--broadcast-mb", type=int, default=100)
    ap.add_argument("--queued", type=int, default=100000)
    ap.add_argument("--object-args", type=int, default=10000)
    ap.add_argument("--store-object-kb", type=int, default=128)
    ap.add_argument("--returns", type=int, default=3000)
    args = ap.parse_args()

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.placement_group import (
        placement_group, remove_placement_group,
    )

    def rss_mb():
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS"):
                    return round(int(ln.split()[1]) / 1024, 1)
        return -1.0

    results = {}
    t_boot = time.monotonic()
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": args.cpus_per_raylet,
                                      "num_tpus": 0})
    for _ in range(args.raylets - 1):
        cluster.add_node(num_cpus=args.cpus_per_raylet, num_tpus=0)
    ray_tpu.init(address=cluster.address)
    results["boot_s"] = round(time.monotonic() - t_boot, 2)
    print(f"[scale] {args.raylets} raylets up in {results['boot_s']}s",
          flush=True)

    # ---- phase 1: task throughput (tiny same-shape tasks) ----------------
    @ray_tpu.remote
    def nop(i):
        return i

    # Warm the worker pools so the phase measures dispatch, not spawns.
    ray_tpu.get([nop.remote(i) for i in range(args.raylets * 4)],
                timeout=300)
    t0 = time.monotonic()
    refs = [nop.remote(i) for i in range(args.tasks)]
    out = ray_tpu.get(refs, timeout=1200)
    dt = time.monotonic() - t0
    assert len(out) == args.tasks
    results["tasks"] = args.tasks
    results["tasks_per_s"] = round(args.tasks / dt, 1)
    print(f"[scale] {args.tasks} tasks in {dt:.1f}s "
          f"({results['tasks_per_s']}/s)", flush=True)

    # ---- phase 3: actors ------------------------------------------------
    # Fractional CPUs: the envelope measures actor COUNT and call
    # throughput, not CPU capacity — 500 one-CPU actors can't fit a
    # 16-CPU test host (they'd queue forever).
    # max_restarts: a 10^3-actor spawn storm on an oversubscribed host
    # can lose a worker to the environment (observed: a libc segfault
    # under fork pressure) — a real cluster rides through exactly this
    # via actor restart, so the envelope measures WITH fault tolerance
    # on and reports the death count instead of aborting.
    @ray_tpu.remote(num_cpus=0.02, max_restarts=2, max_task_retries=2)
    class Echo:
        def ping(self, x=0):
            return x

    # Bring-up is batched + parallel: ONE register_actors GCS RPC admits
    # the whole fleet, then every ping is in flight before the first get
    # (the r5 regression was this barrier run sequentially: submit, get,
    # submit, get — 500 serialized round-trips on top of worker spawns).
    t0 = time.monotonic()
    actors = Echo.remote_many(args.actors)
    results["actors_register_s"] = round(time.monotonic() - t0, 2)
    pings = [a.ping.remote() for a in actors]
    ready, deaths = 0, 0
    for ref in pings:
        try:
            ray_tpu.get(ref, timeout=3600)
            ready += 1
        except Exception:
            deaths += 1
    dt = time.monotonic() - t0
    assert ready >= args.actors * 0.99, (
        f"only {ready}/{args.actors} actors became ready")
    results["actors"] = ready
    results["actor_deaths"] = deaths
    results["actors_ready_s"] = round(dt, 1)
    results["actors_per_s"] = round(ready / dt, 1)
    print(f"[scale] {ready}/{args.actors} actors ready in {dt:.1f}s "
          f"({results['actors_per_s']}/s, {deaths} deaths, register "
          f"{results['actors_register_s']}s)", flush=True)

    t0 = time.monotonic()
    calls = [actors[i % len(actors)].ping.remote(i)
             for i in range(args.actor_calls)]
    ok = 0
    for ref in calls:
        try:
            ray_tpu.get(ref, timeout=1200)
            ok += 1
        except Exception:
            pass
    dt = time.monotonic() - t0
    assert ok >= args.actor_calls * 0.99, f"{ok}/{args.actor_calls}"
    results["actor_calls"] = ok
    results["actor_calls_per_s"] = round(ok / dt, 1)
    print(f"[scale] {ok}/{args.actor_calls} actor calls "
          f"({results['actor_calls_per_s']}/s)", flush=True)
    for a in actors:
        ray_tpu.kill(a)
    del actors

    # ---- phase 4: placement groups --------------------------------------
    t0 = time.monotonic()
    pgs = [placement_group([{"CPU": 1}], strategy="PACK")
           for _ in range(args.pgs)]
    for pg in pgs:
        pg.wait(timeout_seconds=600)
    dt = time.monotonic() - t0
    results["pgs"] = args.pgs
    results["pgs_per_s"] = round(args.pgs / dt, 1)
    print(f"[scale] {args.pgs} PGs ready in {dt:.1f}s "
          f"({results['pgs_per_s']}/s)", flush=True)
    for pg in pgs:
        remove_placement_group(pg)

    # ---- phase 5: broadcast ---------------------------------------------
    import numpy as np

    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    mb = args.broadcast_mb
    if mb:  # --broadcast-mb 0 disables the phase like the other knobs
        blob = ray_tpu.put(
            np.ones((mb, 1024, 128), dtype=np.float64))  # mb MiB

        @ray_tpu.remote
        def digest(arr):
            return float(arr[0, 0, 0]) + arr.shape[0]

        t0 = time.monotonic()
        node_ids = [n["NodeID"] for n in ray_tpu.nodes() if n.get("Alive")]
        refs = [digest.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=bytes.fromhex(nid), soft=False)).remote(blob)
            for nid in node_ids]
        out = ray_tpu.get(refs, timeout=1200)
        dt = time.monotonic() - t0
        assert all(v == 1.0 + mb for v in out)
        results["broadcast_mb"] = mb
        results["broadcast_nodes"] = len(node_ids)
        results["broadcast_s"] = round(dt, 2)
        results["broadcast_mb_per_s"] = round(mb * len(node_ids) / dt, 1)
        print(f"[scale] {mb}MiB broadcast to {len(node_ids)} nodes in "
              f"{dt:.2f}s ({results['broadcast_mb_per_s']} MiB/s "
              f"aggregate)", flush=True)

    # ---- phase 6: per-node object envelope -------------------------------
    # Reference rows (release/benchmarks/README.md:22-31): 10k+ object
    # args to ONE task, 3k+ returns from ONE task, 10k+ store objects in
    # one get.
    if args.object_args:
        # STORE-backed payloads (above max_direct_call_object_size =
        # 100 KiB), so this exercises 10k shared-memory objects, 10k
        # store dependency resolutions into one lease, and one get over
        # 10k store entries — the strict version of the reference rows.
        # The consumer is pinned to the owner's node: the envelope is
        # per-node, not a cross-node transfer benchmark.
        kb = args.store_object_kb
        payload = b"x" * (kb * 1024)
        t0 = time.monotonic()
        arg_refs = [ray_tpu.put(payload) for _ in range(args.object_args)]
        t_put = time.monotonic() - t0

        @ray_tpu.remote
        def count_args(*parts):
            return sum(len(p) for p in parts)

        # Pin to the DRIVER's node (where the puts landed): hard
        # affinity, or the phase silently becomes a 1.25 GiB cross-node
        # transfer instead of the per-node envelope it claims to be.
        from ray_tpu._private.worker import global_worker

        my_node = global_worker().node_id
        t0 = time.monotonic()
        total = ray_tpu.get(
            count_args.options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    node_id=my_node, soft=False))
            .remote(*arg_refs), timeout=1800)
        dt = time.monotonic() - t0
        assert total == args.object_args * kb * 1024
        results["object_args"] = args.object_args
        results["object_args_kb"] = kb
        results["object_args_put_per_s"] = round(args.object_args / t_put, 1)
        results["object_args_call_s"] = round(dt, 2)
        print(f"[scale] {args.object_args} x {kb}KiB store args to one "
              f"task: puts {results['object_args_put_per_s']}/s, call "
              f"{dt:.2f}s", flush=True)

        t0 = time.monotonic()
        vals = ray_tpu.get(arg_refs, timeout=1800)
        dt = time.monotonic() - t0
        assert len(vals) == args.object_args
        results["get_many"] = args.object_args
        results["get_many_per_s"] = round(args.object_args / dt, 1)
        print(f"[scale] one get over {args.object_args} store objects in "
              f"{dt:.2f}s ({results['get_many_per_s']}/s)", flush=True)
        del arg_refs, vals

    if args.returns:
        @ray_tpu.remote(num_returns=args.returns)
        def fan_out():
            return tuple(range(args.returns))

        t0 = time.monotonic()
        refs = fan_out.remote()
        out = ray_tpu.get(refs, timeout=1800)
        dt = time.monotonic() - t0
        assert list(out) == list(range(args.returns))
        results["returns"] = args.returns
        results["returns_s"] = round(dt, 2)
        print(f"[scale] {args.returns} returns from one task in "
              f"{dt:.2f}s", flush=True)

    # ---- final phase: queued depth (the long soak runs LAST: it is the
    # reference's separate many-tasks release test, and running it before
    # the actor storm leaves a 600-process host mid-collapse for the
    # phases that follow) (submit >> capacity, then drain) ----------
    if args.queued:
        t0 = time.monotonic()
        refs = [nop.remote(i) for i in range(args.queued)]
        t_submit = time.monotonic() - t0
        out = ray_tpu.get(refs, timeout=3600)
        dt = time.monotonic() - t0
        assert len(out) == args.queued
        results["queued"] = args.queued
        results["queued_submit_per_s"] = round(args.queued / t_submit, 1)
        results["queued_drain_per_s"] = round(args.queued / dt, 1)
        results["rss_mb_after_queued"] = rss_mb()
        print(f"[scale] {args.queued} queued: submit "
              f"{results['queued_submit_per_s']}/s, drain "
              f"{results['queued_drain_per_s']}/s "
              f"(driver RSS {results['rss_mb_after_queued']} MB)",
              flush=True)


    ray_tpu.shutdown()
    cluster.shutdown()
    print("SCALE-JSON: " + json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
