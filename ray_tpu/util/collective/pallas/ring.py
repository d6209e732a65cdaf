"""ICI ring collectives built on one Pallas TPU remote-copy kernel.

Every collective here is a schedule of single ring hops.  A hop is one
`pallas_call` (`_permute_block`) that runs per-device under `shard_map`
over one mesh axis and moves a block to its right neighbour with
`pltpu.make_async_remote_copy` (the ICI RDMA primitive, SNIPPETS [1][2]).
Source and destination stay in HBM (`memory_space=pl.ANY`): the DMA
engine copies HBM to remote HBM directly, so a hop uses no VMEM and its
message size is bounded by HBM alone.  The reduction between hops is
plain XLA (`dynamic_slice` + combine + `dynamic_update_slice`), which the
TPU compiler runs at HBM bandwidth without any staging the kernel would
have to size by hand.

Hand-shake: before a device writes into its right neighbour's output
buffer it waits, on the kernel's barrier semaphore, for that neighbour's
"entered" signal (each device signals its LEFT neighbour once per call).
Without it a fast sender could land data in memory the neighbour's
previous program still owns.  The signal is one-directional on purpose:
credits then come from exactly one device, in kernel order, so a
neighbour that is already one call ahead cannot satisfy this call's wait.
The exit condition (own send drained, own receive landed) needs no second
barrier.  The old interpreter does not model barrier semaphores and runs
devices in lockstep, so the hand-shake is compiled out under
``interpret=True``.

Layout contract: hops see a 2-D `(rows, LANES)` block; the public
wrappers flatten, pad and restore arbitrary pytree-leaf shapes around
that.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

# TPU vector lane count — the minor dim of every kernel block (pallas guide:
# last dim should be a multiple of 128 on real hardware; the interpreter
# does not care but we keep one layout for both paths).
LANES = 128

_COMBINE: dict = {
    "sum": lambda a, b: a + b,
    "max": jnp.maximum,
    "min": jnp.minimum,
    "prod": lambda a, b: a * b,
}


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "").lower() in ("1", "true", "yes", "on")


def select_impl(requested: str = "auto") -> str:
    """Resolve a collective implementation name.

    ``auto`` → ``pallas`` on a TPU backend, ``pallas_interpret`` when
    ``RAY_TPU_PALLAS_INTERPRET=1`` forces the CPU interpreter (tests), and
    ``lax`` otherwise (the automatic off-TPU fallback demanded by the
    backend registry).  Explicit names pass through after validation.
    """
    valid = ("auto", "pallas", "pallas_interpret", "lax")
    if requested not in valid:
        raise ValueError(f"impl must be one of {valid}, got {requested!r}")
    if requested != "auto":
        return requested
    if jax.default_backend() == "tpu":
        return "pallas"
    if _env_flag("RAY_TPU_PALLAS_INTERPRET"):
        return "pallas_interpret"
    return "lax"


# ---------------------------------------------------------------------------
# The one kernel: a single ring hop, HBM to the right neighbour's HBM.
# ---------------------------------------------------------------------------

# Kernels that share a `collective_id` share one barrier semaphore.  Every
# hop is the same kernel, issued in the same order on every device, so one
# id serves them all (see the hand-shake argument in the module docstring).
_COLLECTIVE_ID = 0


def _permute_kernel(n, axis_name, interpret, in_ref, out_ref,
                    send_sem, recv_sem):
    """One ring hop: send the whole block to the right neighbour, return
    what the left neighbour sent (the SNIPPETS [2] right-permute shape).
    Neighbours are addressed by mesh coordinate along `axis_name`, so the
    hop stays inside its own ring on a multi-axis mesh."""
    my = lax.axis_index(axis_name)
    right = lax.rem(my + 1, n)
    if interpret:
        # The interpreter discharges a remote copy over the one bound
        # axis and takes the neighbour's index on it.
        device_id, id_type = right, pltpu.DeviceIdType.LOGICAL
    else:
        device_id, id_type = {axis_name: right}, pltpu.DeviceIdType.MESH
        left = lax.rem(my + n - 1, n)
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: left},
                               device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, 1)
    rdma = pltpu.make_async_remote_copy(
        src_ref=in_ref,
        dst_ref=out_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=device_id,
        device_id_type=id_type,
    )
    rdma.start()
    rdma.wait()


def _permute_block(x, axis_name, n, interpret):
    kernel = functools.partial(_permute_kernel, n, axis_name, interpret)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            collective_id=_COLLECTIVE_ID),
        name="ring_hop",
    )(x)


# ---------------------------------------------------------------------------
# Shape adaptation: arbitrary leaf -> padded (rows, LANES) block and back.
# ---------------------------------------------------------------------------

def _to_block(x, multiple):
    """Flatten to (rows, LANES) with rows % multiple == 0 (zero padded)."""
    flat = x.reshape(-1)
    per_row_group = multiple * LANES
    padded = ((flat.size + per_row_group - 1) // per_row_group) \
        * per_row_group
    if padded != flat.size:
        flat = jnp.pad(flat, (0, padded - flat.size))
    return flat.reshape(-1, LANES), x.shape, x.size


def _from_block(block, shape, size):
    return block.reshape(-1)[:size].reshape(shape)


def _slabs_to_block(x, n):
    """Pad each of the `n` leading-dim slabs of `x` independently so ring
    chunk `i` is exactly slab `i` (+ trailing zeros) — repacking across
    slab boundaries would hand rank i the wrong elements.  Returns the
    block and (shard_shape, per_shard) to undo it."""
    shard_shape = (x.shape[0] // n,) + x.shape[1:]
    per_shard = _numel(shard_shape)
    slabs = x.reshape(n, per_shard)
    padded = ((per_shard + LANES - 1) // LANES) * LANES
    if padded != per_shard:
        slabs = jnp.pad(slabs, ((0, 0), (0, padded - per_shard)))
    return slabs.reshape(n * (padded // LANES), LANES), shard_shape, \
        per_shard


def _numel(shape) -> int:
    size = 1
    for d in shape:
        size *= int(d)
    return size


def _norm_op(op: str) -> str:
    op = op.lower()
    if op == "mean":
        op = "avg"
    if op not in ("sum", "avg", "max", "min", "prod"):
        raise ValueError(f"unsupported reduce op {op!r}")
    return op


def _check_divisible(x, n):
    if x.shape[0] % n:
        raise ValueError(
            f"reduce_scatter leading dim {x.shape[0]} not divisible by "
            f"ring size {n}")


# ---------------------------------------------------------------------------
# Hop schedules.  A collective can be ISSUED early (``start_*``: places hop
# 0 in the graph depending only on its payload) and AWAITED late
# (``wait_*``: runs the remaining hops and materializes the result).
# Compute traced between the two calls has no data dependency on the
# in-flight hops, which is exactly the freedom XLA's latency-hiding
# scheduler needs to run DMA under compute.  The monolithic entry points
# below are start immediately followed by wait, so the two spellings are
# the same hops in the same order and agree bit for bit (tier-1 asserts
# it).  Handles are trace-scoped Python objects, not pytrees: start and
# wait must happen inside the same traced function.
# ---------------------------------------------------------------------------

class SplitPhaseHandle:
    """An in-flight split-phase ring collective.

    Plain Python object, deliberately NOT a pytree: it holds traced
    arrays, so it is only valid between a ``start_*`` and the matching
    ``wait_*`` inside the same traced function.  Every ``start_*`` MUST
    be balanced by a ``wait_*`` (graftlint's ``collective-split-phase``
    rule enforces this statically).
    """

    __slots__ = ("kind", "axis_name", "n", "op", "impl", "buf",
                 "hops_done", "meta")

    def __init__(self, kind, axis_name, n, op, impl):
        self.kind = kind
        self.axis_name = axis_name
        self.n = n
        self.op = op
        self.impl = impl
        self.buf = None
        self.hops_done = 0
        self.meta = None


def _chunk(block, idx, n):
    rows = block.shape[0] // n
    return lax.dynamic_slice(
        block, (idx * rows, 0), (rows,) + block.shape[1:])


def _rs_hop(block, t, n, axis_name, combine, send):
    """Reduce-scatter hop `t`: chunk ``my-t-1`` goes right through
    `send`, the chunk arriving from the left is combined into
    ``my-t-2``.  The schedule ends with the fully reduced chunk `my` on
    device `my`, matching `lax.psum_scatter` ownership."""
    my = lax.axis_index(axis_name)
    rows = block.shape[0] // n
    send_idx = lax.rem(my - t - 1 + n, n)
    recv_idx = lax.rem(my - t - 2 + 2 * n, n)
    received = send(_chunk(block, send_idx, n))
    return lax.dynamic_update_slice(
        block, combine(_chunk(block, recv_idx, n), received),
        (recv_idx * rows, 0))


def _ag_hop(out, t, n, axis_name, send):
    """Allgather hop `t`: chunk ``my-t`` goes right, the arriving chunk
    lands at ``my-t-1``."""
    my = lax.axis_index(axis_name)
    rows = out.shape[0] // n
    send_idx = lax.rem(my - t + n, n)
    recv_idx = lax.rem(my - t - 1 + n, n)
    received = send(_chunk(out, send_idx, n))
    return lax.dynamic_update_slice(out, received, (recv_idx * rows, 0))


def _sender(axis_name, n, impl):
    return functools.partial(_permute_block, axis_name=axis_name, n=n,
                             interpret=(impl == "pallas_interpret"))


def start_ring_reduce_scatter(x, axis_name: str, *, n: int,
                              op: str = "sum", impl: str = "auto"
                              ) -> SplitPhaseHandle:
    """Issue a reduce-scatter (same contract as `ring_reduce_scatter`:
    leading dim divisible by `n`, rank `i` receives slab `i`).  Hop 0 is
    placed in the graph now; the rest run at `wait_ring_reduce_scatter`."""
    op = _norm_op(op)
    _check_divisible(x, n)
    impl = select_impl(impl)
    h = SplitPhaseHandle("reduce_scatter", axis_name, n, op, impl)
    if impl == "lax" or n == 1:
        h.buf = x
        return h
    block, shard_shape, per_shard = _slabs_to_block(x, n)
    h.meta = (shard_shape, per_shard)
    h.buf = _rs_hop(block, 0, n, axis_name,
                    _COMBINE["sum" if op == "avg" else op],
                    _sender(axis_name, n, impl))
    h.hops_done = 1
    return h


def wait_ring_reduce_scatter(h: SplitPhaseHandle):
    """Await a `start_ring_reduce_scatter`: run the remaining hops and
    return this rank's reduced slab."""
    n, op, axis_name = h.n, h.op, h.axis_name
    if h.impl == "lax" or n == 1:
        out = lax.psum_scatter(h.buf, axis_name, scatter_dimension=0,
                               tiled=True)
        if op == "avg":
            out = out / n
        return out
    combine = _COMBINE["sum" if op == "avg" else op]
    send = _sender(axis_name, n, h.impl)
    block = h.buf
    for t in range(h.hops_done, n - 1):
        block = _rs_hop(block, t, n, axis_name, combine, send)
    mine = _chunk(block, lax.axis_index(axis_name), n)
    shard_shape, per_shard = h.meta
    result = mine.reshape(-1)[:per_shard].reshape(shard_shape)
    if op == "avg":
        result = result / n
    return result


def start_ring_allgather(x, axis_name: str, *, n: int,
                         impl: str = "auto") -> SplitPhaseHandle:
    """Issue an allgather of this rank's shard `x` (same contract as
    `ring_allgather`: result stacks shards on a new leading axis)."""
    impl = select_impl(impl)
    h = SplitPhaseHandle("allgather", axis_name, n, "sum", impl)
    if impl == "lax" or n == 1:
        h.buf = x
        return h
    block, shape, size = _to_block(x, 1)
    rows = block.shape[0]
    my = lax.axis_index(axis_name)
    out = jnp.zeros((n * rows,) + block.shape[1:], block.dtype)
    out = lax.dynamic_update_slice(out, block, (my * rows, 0))
    h.meta = (shape, size, rows)
    h.buf = _ag_hop(out, 0, n, axis_name, _sender(axis_name, n, impl))
    h.hops_done = 1
    return h


def wait_ring_allgather(h: SplitPhaseHandle):
    """Await a `start_ring_allgather`: remaining hops + restack shards."""
    n, axis_name = h.n, h.axis_name
    if h.impl == "lax" or n == 1:
        return lax.all_gather(h.buf, axis_name, tiled=False)
    send = _sender(axis_name, n, h.impl)
    out = h.buf
    for t in range(h.hops_done, n - 1):
        out = _ag_hop(out, t, n, axis_name, send)
    shape, size, rows = h.meta
    pieces = [
        _from_block(out[i * rows:(i + 1) * rows], shape, size)
        for i in range(n)
    ]
    return jnp.stack(pieces, axis=0)


def start_ring_permute(x, axis_name: str, *, n: int,
                       impl: str = "auto") -> SplitPhaseHandle:
    """Issue a right-rotation: rank `i` sends `x` to rank `(i+1) % n` and
    will receive rank `(i-1) % n`'s payload at the wait.  This is the KV
    block exchange of ring attention: issue before the attention block
    compute, await after, and the hop rides under the matmuls."""
    impl = select_impl(impl)
    h = SplitPhaseHandle("permute", axis_name, n, "sum", impl)
    if n == 1:
        h.buf = x
        h.impl = "lax"  # identity; wait returns buf as-is
        return h
    if impl == "lax":
        perm = [(i, (i + 1) % n) for i in range(n)]
        h.buf = lax.ppermute(x, axis_name, perm)
        return h
    block, shape, size = _to_block(x, 1)
    h.meta = (shape, size)
    h.buf = _sender(axis_name, n, impl)(block)
    return h


def wait_ring_permute(h: SplitPhaseHandle):
    """Await a `start_ring_permute`: return the left neighbour's payload."""
    if h.impl == "lax" or h.n == 1:
        return h.buf
    shape, size = h.meta
    return _from_block(h.buf, shape, size)


# ---------------------------------------------------------------------------
# Monolithic entry points: the same hops, issued and awaited in one call.
# ---------------------------------------------------------------------------

def ring_reduce_scatter(x, axis_name: str, *, n: int, op: str = "sum",
                        impl: str = "auto"):
    """`lax.psum_scatter(..., tiled=True)`-shaped reduce-scatter along the
    leading dim, which must be divisible by `n`: rank `i` gets the reduced
    slab ``x[i*rows:(i+1)*rows]``."""
    return wait_ring_reduce_scatter(
        start_ring_reduce_scatter(x, axis_name, n=n, op=op, impl=impl))


def ring_allgather(x, axis_name: str, *, n: int, impl: str = "auto"):
    """`lax.all_gather`-shaped allgather: per-rank shards stacked along a
    new leading axis of size `n`."""
    return wait_ring_allgather(
        start_ring_allgather(x, axis_name, n=n, impl=impl))


def ring_allreduce(x, axis_name: str, *, n: int, op: str = "sum",
                   impl: str = "auto"):
    """`lax.psum`-shaped allreduce over mesh axis `axis_name` (size `n`,
    required statically for the ring schedule).  Call under `shard_map`.
    Reduce-scatter sweep + allgather sweep over one padded block: 2(n-1)
    hops, each moving 1/n of it (bandwidth-optimal)."""
    op = _norm_op(op)
    impl = select_impl(impl)
    if impl == "lax" or n == 1:
        return _lax_allreduce(x, axis_name, op)
    combine = _COMBINE["sum" if op == "avg" else op]
    send = _sender(axis_name, n, impl)
    block, shape, size = _to_block(x, n)
    for t in range(n - 1):
        block = _rs_hop(block, t, n, axis_name, combine, send)
    for t in range(n - 1):
        block = _ag_hop(block, t, n, axis_name, send)
    out = _from_block(block, shape, size)
    if op == "avg":
        out = out / n
    return out


def _lax_allreduce(x, axis_name, op):
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "avg":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    # product: log-space tricks are lossy; use all_gather + reduce.
    gathered = lax.all_gather(x, axis_name)
    return jnp.prod(gathered, axis=0)


# ---------------------------------------------------------------------------
# Driver-side convenience: run a ring collective over a global array.
# ---------------------------------------------------------------------------

def shard_map_collective(fn: Callable[..., Any], mesh: Mesh,
                         axis_name: str) -> Callable[..., Any]:
    """Wrap a per-shard collective `fn(x)` for global arrays sharded over
    `axis_name` (jit + shard_map with the varying-axes check off, since
    Pallas kernels are opaque to it)."""
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name),
        check_vma=False))
