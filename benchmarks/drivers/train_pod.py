"""Driver `train_pod`: one `run_pod_training` job in this process, with
the program's defaults (goodput fence on, AdamW at its default rate).

`run_pod_training` takes a count of steps, not a time, and makes its own
batch and its own parameters from the seed.  So the driver calls it
once briefly (compile and warm-up: set-up), reads a step's wall time,
and calls it again with `steps = floor(seconds / step)`.  Every step's
end comes from the `report` callback: the window runs from the first
`report` of that second call to its last; the second call's own
re-trace and warm-up step are set-up.  The driver adds no loop.

Before either call the plain reference walks the first steps of the
same job (same seed, same batch, float32, no kernels) and is then
dropped, because the program's state fills the chip.

Cell file keys: `job` (`batch_sequences`, `probe_steps`), `check`
(`steps`, `limit`), `trace` (`steps`).  The configuration's `train`
group gives the layout (`mesh_axes`, `weight_update`, `attn_impl`,
`remat`) and `precision` the parameter type.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

import counts
import stats as S
import traffic as T


def _reference(ctx, c, tokens_host, pseed, devices, steps):
    """L(p_0) .. L(p_steps) of the plain reference; parameters sharded
    over all chips on their widest divisible dimension (its own layout:
    plain `jit`, no kernels, no hand-written collectives)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ref = ctx.reference
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("x",))

    def spec(shape):
        if n == 1:
            return NamedSharding(mesh, P())
        dims = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in dims:
            if shape[i] % n == 0 and shape[i] >= 1024:
                return NamedSharding(mesh, P(*[("x" if j == i else None)
                                               for j in range(len(shape))]))
        return NamedSharding(mesh, P())

    shardings = jax.tree.map(spec, ref.shapes(c),
                             is_leaf=lambda x: isinstance(x, tuple))
    params = ref.init_as_trainer(c, pseed, jnp.float32, shardings)
    bsh = NamedSharding(mesh, P("x") if tokens_host.shape[0] % n == 0
                        and n > 1 else P())
    tokens = jax.device_put(tokens_host, bsh)
    out = ref.adamw_trajectory(c, params, tokens, steps)
    del params, tokens
    return out


def run(ctx) -> Dict[str, Any]:
    import jax

    from ray_tpu._private import compile_cache
    from ray_tpu.train.jax_backend import run_pod_training

    cell, c, fam, log = ctx.cell, ctx.config, ctx.family, ctx.log
    compile_cache.configure()
    mix = T.load(ctx.workload["traffic"])
    positions = int(cell.get("positions", mix["positions"]))
    job, tc, ck = cell["job"], c["train"], cell["check"]
    B = int(job["batch_sequences"])
    devices = jax.devices()[: ctx.chips]
    pseed = ctx.seed % (2 ** 31 - 1)
    stated = c["precision"]["parameters"]
    mc = fam.model_config(
        c, max_seq_len=positions, compute_dtype=c["precision"]["compute"],
        param_dtype="bfloat16" if ctx.control else stated,
        attn_impl=tc["attn_impl"], remat=tc["remat"])

    # ---- reference first: the same batch the program will draw
    tokens_host = np.random.RandomState(pseed).randint(
        0, c["vocab_size"], (B, positions + 1)).astype("int32")
    t0 = time.monotonic()
    ref_out = _reference(ctx, c, tokens_host, pseed, devices, ck["steps"])
    log(f"reference {ck['steps']} steps in {time.monotonic() - t0:.1f}s: "
        f"{ref_out}")

    common = dict(model_config=mc, mesh_axes=dict(tc["mesh_axes"]),
                  devices=devices, batch_size=B, seq_len=positions + 1,
                  weight_update=tc["weight_update"], seed=pseed)

    # ---- first call: compile + warm-up + a few steps (all set-up)
    probe: List[float] = []
    s1 = run_pod_training(steps=int(job.get("probe_steps", 3)),
                          report=lambda m: probe.append(m["loss"]), **common)
    step_s = S.median(s1["step_walls"])
    n = max(3, int(math.floor(float(ctx.seconds) / step_s)))
    log(f"probe call done at +{ctx.since_start():.1f}s: step "
        f"{step_s * 1e3:.1f} ms, losses {probe}; measuring {n} steps")
    cache_w: Dict[str, int] = {}

    # ---- second call: the window
    marks = {"on": False}
    tr = cell.get("trace", {})
    tracer = ctx.make_tracer(0.0, 0.0, marks) if ctx.trace else None
    k0, k1 = n // 2, n // 2 + int(tr.get("steps", 5))
    ends: List[float] = []
    losses: List[float] = []

    def report(m):
        now = time.monotonic()
        if not ends:
            ctx.window_opens(now)
            cache_w.update(compile_cache.stats())
        ends.append(now)
        losses.append(m["loss"])
        if marks["on"]:
            with jax.profiler.TraceAnnotation("bench:step"):
                pass
        if tracer is not None:
            if len(ends) == k0:
                tracer.begin()
            elif len(ends) == k1:
                tracer.end()

    s2 = run_pod_training(steps=n, report=report, **common)
    if tracer is not None and marks["on"]:
        tracer.end()
    cache0, cache1 = dict(cache_w), compile_cache.stats()
    window_s = ends[-1] - ends[0]
    steps_in = len(ends) - 1
    tokens_per_step = B * positions
    finite = [math.isfinite(x) for x in losses]

    # ---- correct
    N = counts.total_params(c)
    # every parameter and each of its two moments is held at least once
    # among the chips: in float32 that is 4 N and 8 N bytes (the
    # program's summary says where its state lives after the last step)
    pbytes = sum(s2["state_bytes_per_device"]["params"].values())
    obytes = sum(s2["state_bytes_per_device"]["opt_state"].values())
    state_ratio = min(pbytes / (4.0 * N), obytes / (8.0 * N))
    k = ck["steps"]
    rel = [abs(p - r) / abs(r) for p, r in zip(probe[:k], ref_out["losses"][1:])]
    same_again = [abs(a - b) / abs(b) for a, b in zip(losses[:k], probe[:k])]
    falling = all(b <= a for a, b in zip(losses[:3], losses[1:3]))
    print(f"COMPARED loss_rel_diff={max(rel)!r} limit={ck['limit']!r} "
          f"(program {probe[:k]} reference {ref_out['losses'][1:]}; second "
          f"call against first {same_again}); state_bytes_ratio="
          f"{state_ratio!r} limit>=0.999 (the configuration states "
          f"{stated} parameters and moments); finite={all(finite)} "
          f"non_increasing_first_three={falling}", flush=True)
    correct = (max(rel) <= ck["limit"] and state_ratio >= 0.999
               and all(finite) and falling
               and (ctx.rehearse or bool(s2["step_tpu_custom_calls"])))
    print(f"STEPS {steps_in} in {window_s:.3f}s; step walls p50 "
          f"{S.median(s2['step_walls']) * 1e3:.2f} ms; flash kernels in the "
          f"step program: {s2['step_tpu_custom_calls']}; compiles in window: "
          f"{cache1['misses'] - cache0['misses']} misses "
          f"{cache1['hits'] - cache0['hits']} hits; goodput {s2.get('goodput', {}).get('goodput_ratio')}",
          flush=True)

    records = {
        "step_flops": counts.train_step_flops(c, B, positions),
        "cache_at_window": cache0, "cache_after": cache1,
        "marker_rules": {"bench:step": "train_step", "*": "other"},
        "trace_host_window": tracer.host_window if tracer else None,
        "losses": losses, "summary": {k: v for k, v in s2.items()
                                      if k not in ("goodput",)},
    }
    return {"correct": bool(correct), "attempted": steps_in,
            "failed": sum(1 for f in finite[1:] if not f),
            "e2e": {"train_tokens_per_s": steps_in * tokens_per_step / window_s},
            "records": records, "trace_dir": tracer.dir if tracer else None}
