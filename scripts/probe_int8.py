"""int8-vs-bf16 MXU probe on the real chip (PERF.md round-4 follow-up).

Methodology per bench.py: each measurement is one jitted multi-iteration
call, synchronized by a scalar fetch, min over rounds.  Run ONLY on an
idle host (suite contention invalidates host-clock timings).

Three cases on the flagship MLP geometry (4096 x 11008):
  A. bf16 matmul chain                      (the current train-step mode)
  B. int8 x int8 -> int32 dot, pre-quantized weights, runtime activation
     quant + dequant                        (weight-only PTQ, fwd path)
  C. pure int8 dot chain                    (upper bound, no quant cost)
"""

import time

import jax
import jax.numpy as jnp
from jax import lax

M, K, N = 4096, 4096, 11008
ITERS = 32
ROUNDS = 4


def timeit(name, fn, *args):
    out = fn(*args)
    _ = float(jnp.sum(out[0] if isinstance(out, tuple) else out))  # sync
    times = []
    for _r in range(ROUNDS):
        t0 = time.perf_counter()
        out = fn(*args)
        _ = float(jnp.sum(out[0] if isinstance(out, tuple) else out))
        times.append((time.perf_counter() - t0) / ITERS)
    t = min(times)
    tflops = 2 * M * K * N / t / 1e12
    print(f"{name:28s} {t * 1e3:8.3f} ms/matmul  {tflops:7.1f} T")
    return t


def main():
    print("device:", jax.devices()[0].device_kind)
    key = jax.random.key(0)
    x = jax.random.normal(key, (M, K), jnp.bfloat16)
    w = jax.random.normal(key, (K, N), jnp.bfloat16) * 0.02
    w8 = jnp.round(w.astype(jnp.float32) * 127 / 0.08).astype(jnp.int8)
    ws = jnp.full((1, N), 0.08 / 127, jnp.float32)
    x8 = jnp.round(x.astype(jnp.float32) * 31).astype(jnp.int8)

    @jax.jit
    def bf16_chain(x, w):
        def body(c, _):
            y = c @ w                       # [M,N] bf16
            # fold back to [M,K] so the chain reuses one weight buffer
            c = y[:, :K] * (1.0 / N ** 0.5)
            return c.astype(jnp.bfloat16), None
        c, _ = lax.scan(body, x, None, length=ITERS)
        return c

    @jax.jit
    def int8_weightonly(x, w8, ws):
        def body(c, _):
            # runtime activation quant (per-row scale) — the real PTQ cost
            s = jnp.max(jnp.abs(c).astype(jnp.float32), axis=-1,
                        keepdims=True) / 127.0
            q = jnp.round(c.astype(jnp.float32) / s).astype(jnp.int8)
            acc = lax.dot_general(q, w8, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
            y = acc.astype(jnp.float32) * s * ws
            c = (y[:, :K] * (1.0 / N ** 0.5)).astype(jnp.bfloat16)
            return c, None
        c, _ = lax.scan(body, x, None, length=ITERS)
        return c

    @jax.jit
    def int8_pure(x8, w8):
        def body(c, _):
            acc = lax.dot_general(c, w8, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
            c = (acc[:, :K] >> 9).astype(jnp.int8)
            return c, None
        c, _ = lax.scan(body, x8, None, length=ITERS)
        return c

    t_bf = timeit("A bf16 chain", bf16_chain, x, w)
    t_wo = timeit("B int8 weight-only PTQ", int8_weightonly, x, w8, ws)
    t_i8 = timeit("C int8 pure (upper bound)", int8_pure, x8, w8)
    print(f"\nspeedup B vs A: x{t_bf / t_wo:.3f}   C vs A: x{t_bf / t_i8:.3f}")
    single_dot()


def single_dot():
    """Cases D/E of the PERF.md table: one 8192^3 dot repeated with a
    varying operand (defeats CSE), minimal non-matmul work — the cleanest
    look at the raw MXU rate per dtype."""
    global M, K, N
    M = K = N = 8192
    key = jax.random.key(0)
    a16 = jax.random.normal(key, (M, K), jnp.bfloat16)
    b16 = jax.random.normal(key, (K, N), jnp.bfloat16)
    a8 = (a16 * 10).astype(jnp.int8)
    b8 = (b16 * 10).astype(jnp.int8)

    @jax.jit
    def d_bf16(a, b):
        def inner(c, i):
            y = (a * (1.0 + i * 1e-6).astype(jnp.bfloat16)) @ b
            return c + y[0, :8].astype(jnp.float32).sum(), None
        c, _ = lax.scan(inner, jnp.float32(0),
                        jnp.arange(ITERS, dtype=jnp.float32))
        return c

    @jax.jit
    def e_int8(a, b):
        def inner(c, i):
            aa = a + (i % 2).astype(jnp.int8)
            y = lax.dot_general(aa, b, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
            return c + y[0, :8].sum(), None
        c, _ = lax.scan(inner, jnp.int32(0),
                        jnp.arange(ITERS, dtype=jnp.int32))
        return c

    t_d = timeit("D bf16 single dot 8192^3", d_bf16, a16, b16)
    t_e = timeit("E int8 single dot 8192^3", e_int8, a8, b8)
    print(f"speedup E vs D: x{t_d / t_e:.3f}")


if __name__ == "__main__":
    main()
