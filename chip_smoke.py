#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the system's two hot paths once, through the entry points a user
calls, at Llama-3-8B widths (depth, batch and KV pool cut to one chip's
16 GB; every cut is printed) with random weights made from ``--seed``:

- serve: ``ray_tpu.init()`` + ``serve.run(build_llm_app(..., num_tpus=1))``
  answers a handful of requests from a replica that owns the chip; the
  tokens must equal greedy ``generate`` on the same weights (computed by a
  fresh child process after the cluster is gone), except where generate's
  own choice is a tie within bfloat16 rounding (``TIE_LOGITS``).
- train: a fresh child runs ``run_pod_training`` for a few steps with
  ``attn_impl="flash"``; the loss must be finite and non-increasing and
  the compiled step must contain the flash kernel.

``--four-chips`` runs only the multi-chip path (sharded and ring-overlap
training against a one-device mesh, ring collectives against ``lax``) and
what it is compared with.

One process uses the chip at a time: this parent process never imports
JAX.  It refuses to run (non-zero exit, no result line) unless JAX's
default platform is ``tpu``.  The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Published widths (models/llama.py::LlamaConfig.llama3_8b / .llama2_7b;
# children assert the two agree).  Plain data: the parent stays off JAX.
LLAMA3_8B_WIDTHS = dict(
    vocab_size=128256, dim=4096, n_heads=32, n_kv_heads=8,
    hidden_dim=14336, rope_theta=500000.0)
LLAMA2_7B_WIDTHS = dict(
    vocab_size=32000, dim=4096, n_heads=32, n_kv_heads=32,
    hidden_dim=11008, rope_theta=10000.0)
PUBLISHED = {"llama3_8b": LLAMA3_8B_WIDTHS, "llama2_7b": LLAMA2_7B_WIDTHS}

# Cuts, each forced by one v5e chip's 15.75 GiB of usable HBM.
SERVE_CUTS = {
    "n_layers": "16 of 32: bf16 weights are 2.1 GB (embed + head) + "
                "0.436 GB a layer; 16 layers = 8.5 GiB",
    "kv_pool": "2560 blocks x 16 tokens = 40960 tokens (2.5 GiB): the "
               "paged decode program holds the pool twice (its layer "
               "scan writes a fresh stacked pool), so the pool may take "
               "half of what the weights leave",
    "max_seq_len": "2048 per slot, 8 slots",
}
TRAIN_CUTS = {
    "n_layers": "2 of 32",
    "batch": "2 sequences x 1024 tokens",
    "param_dtype": "bfloat16 weights and AdamW moments: in float32 the "
                   "1.05 B embedding + head parameters alone need 16.8 GB "
                   "of weights, gradients and moments",
}
FOUR_CHIP_CUTS = {
    "model": "Llama-2-7B widths, not Llama-3-8B: the ring-overlap step "
             "keeps several copies of the flat parameter vector on every "
             "chip, and Llama-3's 1.05 B embedding + head parameters "
             "alone make that vector 2.1 GB",
    "n_layers": "2 of 32",
    "batch": "4 sequences x 512 tokens (one per data shard)",
    "param_dtype": "bfloat16, as in the one-chip train phase",
}


def chip_spec(seed: int) -> dict:
    """What the default (one chip) run drives."""
    model = dict(LLAMA3_8B_WIDTHS, max_seq_len=2048,
                 dtype="bfloat16", param_dtype="bfloat16")
    return {
        "seed": seed,
        "platform": "tpu",
        "num_tpus": 1,
        "serve": {
            "model": dict(model, n_layers=16),
            "engine": {"num_slots": 8, "max_seq_len": 2048,
                       "prefill_buckets": (128, 512),
                       "kv_layout": "paged", "kv_block_size": 16,
                       "num_kv_blocks": 2560},
            # (prompt length, shared-prefix length with the previous one)
            "prompts": [(24, 0), (700, 0), (200, 0), (200, 160)],
            "max_tokens": 8,
        },
        "train": {
            "model": dict(model, n_layers=2, attn_impl="flash"),
            "steps": 3, "batch_size": 2, "seq_len": 1025,
            "expect_kernel": True,
        },
    }


def four_chip_spec(seed: int) -> dict:
    model = dict(LLAMA2_7B_WIDTHS, max_seq_len=1024, n_layers=2,
                 dtype="bfloat16", param_dtype="bfloat16",
                 attn_impl="flash")
    return {
        "seed": seed,
        "platform": "tpu",
        "n_devices": 4,
        "model": model,
        "steps": 3, "batch_size": 4, "seq_len": 513,
        "sharded_mesh": {"data": 2, "fsdp": 1, "tensor": 2},
        "loss_rtol": 2e-2,
        # per-shard float32 message sizes for the ring collectives
        "message_bytes": [1 << 20, 128 << 20],
        "collective_impl": "pallas",
    }


def log(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


# --------------------------------------------------------------------------
# Phases that need JAX.  The script runs each in a child process of its own
# (`run_child`); the CPU rehearsal among the tests calls them directly.
# --------------------------------------------------------------------------

def _model_config(kwargs: dict):
    from ray_tpu.models.llama import LlamaConfig

    config = LlamaConfig(**kwargs)
    for base, widths in PUBLISHED.items():
        if kwargs.get("vocab_size") == widths["vocab_size"]:
            published = getattr(LlamaConfig, base)()
            for name in widths:
                assert getattr(config, name) == getattr(published, name), (
                    f"smoke width {name} drifted from LlamaConfig.{base}")
    return config


def _device_report() -> dict:
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "peak_hbm_gib": round(
                stats.get("peak_bytes_in_use", 0) / 2 ** 30, 3)}


def _require_platform(spec: dict) -> dict:
    report = _device_report()
    if report["platform"] != spec["platform"]:
        raise SystemExit(
            f"chip_smoke: JAX's platform is {report['platform']!r}, not "
            f"{spec['platform']!r}: refusing to run")
    return report


def make_prompts(spec: dict) -> list:
    """Token prompts from the seed (numpy only: the parent calls this)."""
    import numpy as np

    rng = np.random.RandomState(spec["seed"])
    vocab = spec["serve"]["model"]["vocab_size"]
    prompts: list = []
    for length, shared in spec["serve"]["prompts"]:
        fresh = rng.randint(0, vocab, length).tolist()
        prompts.append(prompts[-1][:shared] + fresh[shared:]
                       if shared else fresh)
    return prompts


# A served token may differ from greedy `generate` only where generate's
# own choice is a coin toss: the reference logit of the served token lies
# within this many logits of the reference maximum, given the same prefix.
# Why a margin at all: logits are built from bfloat16 activations, and two
# correct programs that round in a different order (one sequence against
# eight slots, a cache of P+n rows against gathered blocks of 2048) do not
# agree bit for bit on the chip as they do on the CPU.  Why this one: with
# a 0.02-std head over a unit-RMS hidden of width 4096 the logits have a
# standard deviation near 1.3, so the top two of 128256 lie a mean 0.26
# apart and a quarter of all positions closer than 0.08.  The reference
# measures the disagreement of `generate` with ITSELF under a longer cache
# (`self_noise`, printed); the first chip runs read a few hundredths, and
# a served token 0.08 under the maximum.  A wrong row, position or mask
# moves logits by their whole spread and lands several logits under.
TIE_LOGITS = 0.25


def reference_phase(spec: dict) -> dict:
    """Greedy `generate` on the weights the replica made from the seed,
    and — with ``served_tokens`` in the spec — the same prefill and decode
    steps `generate` takes, fed the served tokens: per position the
    reference argmax and how far below the maximum the served token's
    logit is (0 everywhere iff served == generate)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu._private import compile_cache
    from ray_tpu.models.llama import (
        decode_step, generate, init_params, prefill,
    )

    compile_cache.configure()
    _require_platform(spec)
    config = _model_config(spec["serve"]["model"])
    params = init_params(config, jax.random.key(spec["seed"]))
    n = spec["serve"]["max_tokens"]
    gen = jax.jit(lambda p, t: generate(p, t, config, n))

    @functools.partial(jax.jit, static_argnums=3)
    def forced(p, prompt, served, cache_len):
        P = prompt.shape[1]
        logits, cache = prefill(p, prompt, config,
                                max_len=cache_len or P + n)

        def body(carry, i):
            cache, logits = carry
            tok = served[:, i]
            chosen = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
            out = (jnp.argmax(logits, -1), jnp.max(logits, -1) - chosen)
            logits, cache = decode_step(
                p, cache, tok, jnp.full((1,), P, jnp.int32) + i, config)
            return (cache, logits), out

        _, (best, gap) = lax.scan(body, (cache, logits), jnp.arange(n))
        return best[:, 0], gap[:, 0]

    tokens, argmax, gaps, noise = [], [], [], 0.0
    served = spec["serve"].get("served_tokens")
    long_cache = spec["serve"]["engine"]["max_seq_len"]
    for i, prompt in enumerate(make_prompts(spec)):
        prompt = jnp.asarray([prompt], jnp.int32)
        tokens.append([int(t) for t in gen(params, prompt)[0]])
        if served is not None:
            forced_toks = jnp.asarray([served[i]], jnp.int32)
            best, gap = forced(params, prompt, forced_toks, None)
            _, gap_long = forced(params, prompt, forced_toks, long_cache)
            argmax.append([int(t) for t in best])
            gaps.append([round(float(g), 4) for g in gap])
            noise = max(noise, float(jnp.max(jnp.abs(gap - gap_long))))
    return {"tokens": tokens, "forced_argmax": argmax, "forced_gap": gaps,
            "self_noise": round(noise, 4), "device": _device_report(),
            "compile_cache": compile_cache.stats()}


def train_phase(spec: dict) -> dict:
    """A few `run_pod_training` steps on a one-device mesh."""
    import jax

    from ray_tpu._private import compile_cache
    from ray_tpu.train.jax_backend import run_pod_training

    compile_cache.configure()
    _require_platform(spec)
    t = spec["train"]
    losses: list = []
    t0 = time.monotonic()
    summary = run_pod_training(
        model_config=_model_config(t["model"]), mesh_axes={"data": 1},
        devices=jax.devices()[:1], steps=t["steps"],
        batch_size=t["batch_size"], seq_len=t["seq_len"],
        seed=spec["seed"], report=lambda m: losses.append(m["loss"]))
    return {
        "losses": losses, "wall_s": round(time.monotonic() - t0, 1),
        "compile_and_first_step_s": round(
            summary["goodput"]["lost_s"].get("recompiling", 0.0), 1),
        "step_walls_s": [round(w, 3) for w in summary["step_walls"]],
        # an XLA-attention fallback has no `tpu_custom_call` in the step
        "flash_kernel_calls": summary["step_tpu_custom_calls"],
        "device": _device_report(),
        "compile_cache": compile_cache.stats(),
    }


def check_train(spec: dict, out: dict) -> None:
    import math

    losses = out["losses"]
    if len(losses) != spec["train"]["steps"]:
        raise AssertionError(f"expected {spec['train']['steps']} losses, "
                             f"got {losses}")
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if any(b > a for a, b in zip(losses, losses[1:])):
        raise AssertionError(f"loss went up: {losses}")
    if spec["train"]["expect_kernel"] and out["flash_kernel_calls"] < 1:
        raise AssertionError(
            "the compiled train step holds no tpu_custom_call: flash "
            "attention gave way to XLA attention")


def four_chip_phase(spec: dict) -> dict:
    """Sharded and ring-overlap training against a one-device mesh, and
    the ring collectives against `lax`, in one process over four chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax, shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu._private import compile_cache
    from ray_tpu.ops.ring_attention import ring_attention_global
    from ray_tpu.train.jax_backend import run_pod_training
    from ray_tpu.util.collective import pallas as rk

    compile_cache.configure()
    device = _require_platform(spec)
    n = spec["n_devices"]
    if device["count"] < n:
        raise AssertionError(f"need {n} devices, JAX sees {device}")
    devices = jax.devices()[:n]
    config = _model_config(spec["model"])
    impl = spec["collective_impl"]
    out: dict = {"device": device, "legs": {}}

    def leg(name, **kwargs):
        losses: list = []
        t0 = time.monotonic()
        summary = run_pod_training(
            model_config=config, steps=spec["steps"],
            batch_size=spec["batch_size"], seq_len=spec["seq_len"],
            seed=spec["seed"], report=lambda m: losses.append(m["loss"]),
            **{"devices": devices, **kwargs})
        out["legs"][name] = {
            "losses": losses, "mesh": summary["mesh"],
            "state_bytes_per_device": summary["state_bytes_per_device"],
            "wall_s": round(time.monotonic() - t0, 1)}
        log(f"four-chip leg {name}: {out['legs'][name]}")
        return out["legs"][name]

    ref = leg("one_device", mesh_axes={"data": 1}, devices=devices[:1])
    sharded = leg("sharded", mesh_axes=spec["sharded_mesh"],
                  weight_update="sharded")
    overlap = leg("overlap", mesh_axes={"data": n}, overlap=True,
                  collective=impl)
    for name, got in (("sharded", sharded), ("overlap", overlap)):
        np.testing.assert_allclose(
            got["losses"], ref["losses"], rtol=spec["loss_rtol"],
            err_msg=f"{name} leg diverged from the one-device mesh")
        per_dev = got["state_bytes_per_device"]
        if len(per_dev["opt_state"]) != n or not all(
                per_dev["opt_state"].values()):
            raise AssertionError(
                f"{name}: optimizer state is not on all {n} devices: "
                f"{per_dev}")
        one = sum(ref["state_bytes_per_device"]["opt_state"].values())
        if max(per_dev["opt_state"].values()) > 0.75 * one:
            raise AssertionError(
                f"{name}: a device holds {max(per_dev['opt_state'].values())}"
                f" bytes of optimizer state, the one-device run {one}: "
                "the state is replicated, not spread")
        if len(per_dev["params"]) != n or not all(
                per_dev["params"].values()):
            raise AssertionError(
                f"{name}: parameters are not on all {n} devices: {per_dev}")

    # Ring collectives against lax, integer-valued so every order of
    # float adds gives the same bits.
    mesh = Mesh(np.asarray(devices), ("x",))

    def run(fn, x, out_spec=P("x")):
        g = jax.jit(shard_map(fn, mesh=mesh, in_specs=P("x"),
                              out_specs=out_spec, check_vma=False))
        return np.asarray(jax.block_until_ready(g(x)))

    out["collectives"] = {}
    for nbytes in spec["message_bytes"]:
        rows = nbytes // 4 // rk.ring.LANES     # per device
        host = np.random.RandomState(spec["seed"]).randint(
            -8, 9, (n * rows, rk.ring.LANES)).astype(np.float32)
        x = jax.device_put(host, NamedSharding(mesh, P("x")))
        pairs = {
            "allreduce": (
                lambda a: rk.ring_allreduce(a, "x", n=n, impl=impl),
                lambda a: lax.psum(a, "x"), P("x")),
            "reduce_scatter": (
                lambda a: rk.ring_reduce_scatter(a, "x", n=n, impl=impl),
                lambda a: lax.psum_scatter(a, "x", scatter_dimension=0,
                                           tiled=True), P("x")),
            "allgather": (
                lambda a: rk.ring_allgather(a, "x", n=n, impl=impl),
                lambda a: lax.all_gather(a, "x", tiled=False),
                P(None, "x")),
            "permute": (
                lambda a: rk.wait_ring_permute(
                    rk.start_ring_permute(a, "x", n=n, impl=impl)),
                lambda a: lax.ppermute(
                    a, "x", [(i, (i + 1) % n) for i in range(n)]),
                P("x")),
        }
        for name, (ring_fn, lax_fn, spec_out) in pairs.items():
            np.testing.assert_array_equal(
                run(ring_fn, x, spec_out), run(lax_fn, x, spec_out),
                err_msg=f"ring {name} != lax at {nbytes} bytes/shard")
            out["collectives"][f"{name}@{nbytes}"] = "equal"
        # int8 hops: bounded error, not equality (one scale per chunk,
        # 2(n-1) hops, each at most max|partial|/254 per element).
        got = run(lambda a: rk.quantized_ring_allreduce(
            a, "x", n=n, impl=impl), x)
        want = run(lambda a: lax.psum(a, "x"), x)
        bound = 2 * (n - 1) * (8.0 * n) / 254.0 + 1e-3
        err = float(np.max(np.abs(got - want)))
        if not err <= bound:
            raise AssertionError(
                f"quantized allreduce error {err} > bound {bound}")
        out["collectives"][f"quantized_allreduce@{nbytes}"] = {
            "max_abs_err": err, "bound": bound}
        del x

    # The permute kernel under ring attention (forward), against the
    # ppermute ring.
    smesh = Mesh(np.asarray(devices), ("sp",))
    key = jax.random.key(spec["seed"])
    q, k, v = (jax.random.normal(kk, (2, n * 256, 8, 128), jnp.bfloat16)
               for kk in jax.random.split(key, 3))
    got = jax.jit(lambda a, b, c: ring_attention_global(
        a, b, c, smesh, impl=impl))(q, k, v)
    want = jax.jit(lambda a, b, c: ring_attention_global(
        a, b, c, smesh, impl="lax"))(q, k, v)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        err_msg="ring attention: pallas permute != ppermute")
    out["ring_attention"] = "equal"
    out["device"] = _device_report()
    out["compile_cache"] = compile_cache.stats()
    return out


CHILD_PHASES = {"probe": _require_platform, "reference": reference_phase,
                "train": train_phase, "four_chip": four_chip_phase}


# --------------------------------------------------------------------------
# The serve phase runs in the caller (the smoke's parent, or the rehearsal
# test): it only drives the cluster and never touches JAX itself.
# --------------------------------------------------------------------------

def _descendants(root: int) -> list:
    children: dict = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
        children.setdefault(ppid, []).append(int(entry))
    out, stack = [], [root]
    while stack:
        for pid in children.get(stack.pop(), ()):
            out.append(pid)
            stack.append(pid)
    return out


def tpu_library_holders(root: int) -> list:
    """Descendant processes of `root` that have libtpu mapped."""
    holders = []
    for pid in _descendants(root):
        try:
            with open(f"/proc/{pid}/maps") as f:
                if "libtpu" in f.read():
                    holders.append(pid)
        except OSError:
            pass
    return holders


def _dump_worker_logs(session_dir: str, tail: int = 40) -> None:
    """A failed serve phase: show what the cluster's processes last said
    (their logs die with the machine otherwise)."""
    log_dir = os.path.join(session_dir, "logs")
    for name in sorted(os.listdir(log_dir)) if os.path.isdir(log_dir) \
            else ():
        path = os.path.join(log_dir, name)
        with open(path, errors="replace") as f:
            lines = [ln for ln in f.read().splitlines()[-tail:]
                     if ln.strip() and not ln.startswith("::rtpu:task")]
        if lines:
            sys.stderr.write(f"----- {name} (last {len(lines)} lines)\n"
                             + "\n".join(lines) + "\n")


def serve_phase(spec: dict) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app

    s = spec["serve"]
    prompts = make_prompts(spec)
    top_bucket = max(s["engine"]["prefill_buckets"])
    before = set(_descendants(os.getpid()))
    session = ray_tpu.init(num_tpus=spec["num_tpus"] or None)
    try:
        t0 = time.monotonic()
        handle = serve.run(build_llm_app(
            model_config=s["model"], engine_config=s["engine"],
            num_tpus=spec["num_tpus"], quantize="bf16",
            init_seed=spec["seed"], max_ongoing_requests=8), name="llm")
        ready_s = time.monotonic() - t0
        log(f"serve app ready in {ready_s:.1f} s")
        t0 = time.monotonic()
        pending = [handle.remote({
            "prompt": p, "max_tokens": s["max_tokens"],
            "chunked_prefill": len(p) > top_bucket, "timeout_s": 900.0})
            for p in prompts]
        answers = [r.result(timeout=900) for r in pending]
        answer_s = time.monotonic() - t0
        log(f"{len(answers)} requests answered in {answer_s:.1f} s")
        t0 = time.monotonic()
        stats = handle.stats.remote().result(timeout=600)
        log(f"replica stats in {time.monotonic() - t0:.1f} s")
        holders = tpu_library_holders(os.getpid())
    except BaseException:
        _dump_worker_logs(session.get("session_dir", ""))
        raise
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    # The next phase's process needs the chip: every process this one
    # started must be gone first.
    deadline = time.monotonic() + 60
    while (set(_descendants(os.getpid())) - before
           and time.monotonic() < deadline):
        time.sleep(0.2)
    left = sorted(set(_descendants(os.getpid())) - before)
    if left:
        raise AssertionError(f"cluster processes still alive: {left}")
    return {"tokens": [a["tokens"] for a in answers],
            "finish": [a["finish_reason"] for a in answers],
            "replica": stats["device"], "compile_cache":
            stats["compile_cache"], "engine": {
                k: stats.get(k) for k in (
                    "completed", "trace_count", "traces", "prefix")},
            "tpu_library_holders": holders,
            "ready_s": round(ready_s, 1), "answer_s": round(answer_s, 1)}


def check_serve(spec: dict, served: dict, reference: dict) -> None:
    replica = served["replica"]
    if replica["platform"] != spec["platform"]:
        raise AssertionError(
            f"the replica ran on {replica['platform']!r}, not "
            f"{spec['platform']!r}: {replica}")
    others = [p for p in served["tpu_library_holders"]
              if p != replica["pid"]]
    if others:
        raise AssertionError(
            f"processes other than the replica ({replica['pid']}) have "
            f"the TPU library open: {others}")
    if spec["platform"] == "tpu" and (
            replica["pid"] not in served["tpu_library_holders"]):
        raise AssertionError("the replica does not have libtpu mapped")
    if any(len(t) != spec["serve"]["max_tokens"]
           for t in served["tokens"]):
        raise AssertionError(f"short answers: {served['tokens']}")
    # Equal to greedy generate, except where generate itself is a coin
    # toss (see TIE_LOGITS): there the served token must be within the
    # tie margin of the reference maximum, given the same prefix.
    for i, (got, best, gap) in enumerate(zip(
            served["tokens"], reference["forced_argmax"],
            reference["forced_gap"])):
        for pos, (g, b, d) in enumerate(zip(got, best, gap)):
            if g != b and not d <= TIE_LOGITS:
                raise AssertionError(
                    f"request {i} token {pos}: served {g}, greedy "
                    f"generate takes {b}, {d} logits above it (more than "
                    f"a tie, {TIE_LOGITS}):\n"
                    f"  served   {served['tokens']}\n"
                    f"  generate {reference['tokens']}")


def serve_agreement(served: dict, reference: dict) -> dict:
    """How the served tokens compare with greedy `generate`."""
    flat = [(g == b, d) for got, best, gap in zip(
        served["tokens"], reference["forced_argmax"],
        reference["forced_gap"]) for g, b, d in zip(got, best, gap)]
    return {"tokens": len(flat),
            "equal_generate_given_prefix": sum(e for e, _ in flat),
            "requests_equal_generate": sum(
                a == b for a, b in zip(served["tokens"],
                                       reference["tokens"])),
            "largest_gap_of_a_differing_token": max(
                [d for e, d in flat if not e], default=0.0)}


# --------------------------------------------------------------------------
# Parent: orchestration only.
# --------------------------------------------------------------------------

def run_child(phase: str, spec: dict, timeout: float = 1100.0) -> dict:
    """Run one JAX phase in a fresh process that owns the chip while it
    lives; its last stdout line is the phase's JSON result."""
    from ray_tpu._private import compile_cache

    env = compile_cache.child_env(dict(os.environ))
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", phase],
        input=json.dumps(spec), capture_output=True, text=True,
        env=env, timeout=timeout, cwd=REPO)
    lines = proc.stdout.strip().splitlines()
    for line in lines[:-1]:
        print(f"  [{phase}] {line}", flush=True)
    if proc.returncode != 0 or not lines:
        sys.stderr.write(proc.stderr[-6000:])
        raise SystemExit(
            f"chip_smoke: phase {phase!r} failed (exit {proc.returncode})")
    return json.loads(lines[-1])


def _child_main(phase: str) -> None:
    spec = json.loads(sys.stdin.read())
    result = CHILD_PHASES[phase](spec)
    print(json.dumps(result), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip path (needs 4 chips)")
    ap.add_argument("--child", choices=sorted(CHILD_PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return _child_main(args.child)

    sys.path.insert(0, REPO)
    from ray_tpu._private import compile_cache, native_store

    spec = four_chip_spec(args.seed) if args.four_chips \
        else chip_spec(args.seed)
    device = run_child("probe", spec, timeout=300)
    log(f"device: {device}; compile cache: {compile_cache.cache_dir()}")

    if args.four_chips:
        log(f"four-chip cuts: {json.dumps(FOUR_CHIP_CUTS)}")
        out = run_child("four_chip", spec, timeout=3000)
        log(f"collectives: {json.dumps(out['collectives'])}")
        log(f"ring attention: {out['ring_attention']}; compile cache: "
            f"{out['compile_cache']}; peak HBM "
            f"{out['device']['peak_hbm_gib']} GiB on device 0")
        device = out["device"]
    else:
        if native_store.load() is None:
            raise SystemExit("chip_smoke: native object store did not "
                             f"build: {native_store.load_error()}")
        log("object store: native (built from native/arena_store.cpp)")
        log(f"serve cuts: {json.dumps(SERVE_CUTS)}")
        served = serve_phase(spec)
        log(f"serve: replica {served['replica']['platform']} "
            f"{served['replica']['kind']!r} pid {served['replica']['pid']},"
            f" ready in {served['ready_s']} s, {len(served['tokens'])} "
            f"requests in {served['answer_s']} s (compiles included), "
            f"peak HBM {served['replica']['peak_hbm_gib']} GiB, engine "
            f"{served['engine']}, compile cache {served['compile_cache']}, "
            f"libtpu open in pids {served['tpu_library_holders']}")
        spec["serve"]["served_tokens"] = served["tokens"]
        reference = run_child("reference", spec)
        log(f"reference: peak HBM {reference['device']['peak_hbm_gib']} "
            f"GiB, compile cache {reference['compile_cache']}, generate "
            f"against itself under a {spec['serve']['engine']['max_seq_len']}"
            f"-row cache moves a token's margin by up to "
            f"{reference['self_noise']} logits")
        check_serve(spec, served, reference)
        log(f"served tokens against greedy generate: "
            f"{serve_agreement(served, reference)} (a differing token "
            f"must be a tie: within {TIE_LOGITS} logits); served "
            f"{served['tokens']}")
        log(f"train cuts: {json.dumps(TRAIN_CUTS)}")
        trained = run_child("train", spec)
        check_train(spec, trained)
        log(f"train: losses {trained['losses']}, compile + first step "
            f"{trained['compile_and_first_step_s']} s, "
            f"{trained['flash_kernel_calls']} tpu_custom_call in the "
            f"step, peak HBM {trained['device']['peak_hbm_gib']} GiB, "
            f"compile cache {trained['compile_cache']}")
        device = trained["device"]

    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)


if __name__ == "__main__":
    main()
