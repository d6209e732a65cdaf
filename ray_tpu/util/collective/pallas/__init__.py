"""Pallas ICI ring collectives.

Hand-written TPU collective kernels built on `pltpu.make_async_remote_copy`
remote-copy hops, runnable under `shard_map` on a mesh axis.  Every
kernel has an `interpret=True` path so the exact same code is testable on
CPU virtual devices, and every public entry point degrades to the
corresponding `jax.lax` collective when Pallas is not viable (non-TPU
backend with interpret disabled).

Public API::

    ring_allreduce(x, axis_name, ...)       # psum-shaped
    ring_allgather(x, axis_name, ...)       # all_gather(tiled=True)-shaped
    ring_reduce_scatter(x, axis_name, ...)  # psum_scatter-shaped
    quantized_ring_allreduce(x, axis_name, ...)  # EQuARX-style int8 ring
    select_impl(...)                        # backend/fallback resolution

Split-phase API (compute/communication overlap) — a collective becomes a
``start_*`` that issues hop 0 and a ``wait_*`` that runs the remaining
hops, so compute traced between the two runs with the wire time hidden
under it.  Every start MUST be balanced by a wait in the same traced
function (graftlint enforces this)::

    h = start_ring_reduce_scatter(x, axis, n=n)   # hop 0 in flight
    y = heavy_compute(...)                        # comm hides under this
    shard = wait_ring_reduce_scatter(h)           # hops 1..n-1 + result
    start_ring_allgather / wait_ring_allgather    # same, allgather
    start_ring_permute / wait_ring_permute        # one-hop KV rotation
    start_quantized_ring_reduce_scatter / wait_quantized_ring_reduce_scatter
    local_quantization_residual(block, n)         # error-feedback increment
"""

from ray_tpu.util.collective.pallas.ring import (
    SplitPhaseHandle, ring_allgather, ring_allreduce, ring_reduce_scatter,
    select_impl, start_ring_allgather, start_ring_permute,
    start_ring_reduce_scatter, wait_ring_allgather, wait_ring_permute,
    wait_ring_reduce_scatter,
)
from ray_tpu.util.collective.pallas.quantized import (
    local_quantization_residual, quantized_ring_allreduce,
    start_quantized_ring_reduce_scatter, wait_quantized_ring_reduce_scatter,
)

__all__ = [
    "ring_allreduce", "ring_allgather", "ring_reduce_scatter",
    "quantized_ring_allreduce", "select_impl", "SplitPhaseHandle",
    "start_ring_reduce_scatter", "wait_ring_reduce_scatter",
    "start_ring_allgather", "wait_ring_allgather",
    "start_ring_permute", "wait_ring_permute",
    "start_quantized_ring_reduce_scatter",
    "wait_quantized_ring_reduce_scatter",
    "local_quantization_residual",
]
