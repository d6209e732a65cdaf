"""Pallas ICI collective backend.

The `pallas` backend shares the XLA group's control plane — rendezvous via
the named coordinator actor, `jax.distributed.initialize`, group meshes —
but routes the data plane through the hand-written ring kernels in
`ray_tpu.util.collective.pallas` (`pltpu.make_async_remote_copy`
double-buffered rings) instead of XLA's stock collectives.  That makes the
wire schedule ours to shape: the EQuARX-style int8 variant halves-to-
quarters allreduce bytes on bandwidth-bound links, something XLA's psum
cannot be told to do.

Implementation resolution per op (see `pallas.ring.select_impl`):
TPU backend → compiled Pallas kernels; CPU with
``RAY_TPU_PALLAS_INTERPRET=1`` → the same kernels under the Pallas
interpreter (what the tier-1 tests exercise); anything else → automatic
fallback to `jax.lax` collectives, so a `pallas` group degrades gracefully
off-TPU rather than failing.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ray_tpu.util.collective.collective_group.xla_collective_group import (
    XLAGroup,
)
from ray_tpu.util.collective.types import ReduceOp

_RING_OPS = {
    ReduceOp.SUM: "sum",
    ReduceOp.AVERAGE: "avg",
    ReduceOp.MIN: "min",
    ReduceOp.MAX: "max",
    ReduceOp.PRODUCT: "prod",
}


class PallasGroup(XLAGroup):
    """Collective group whose device-side ops are Pallas ring kernels.

    Host-level API parity ops accept numpy/jax arrays like `XLAGroup`; the
    real training path pulls `get_mesh()` / `ring_collective()` and runs
    the kernels inside its own jitted step.
    """

    backend_name = "pallas"

    def __init__(self, world_size: int, rank: int, group_name: str,
                 platform: Optional[str] = None,
                 local_device_count: Optional[int] = None,
                 quantized: bool = False):
        super().__init__(world_size, rank, group_name,
                         platform=platform,
                         local_device_count=local_device_count)
        self._quantized = quantized
        self._fn_cache: dict = {}

    # ------------------------------------------------------------ resolution
    def resolved_impl(self) -> str:
        from ray_tpu.util.collective.pallas import select_impl

        return select_impl("auto")

    def uses_pallas(self) -> bool:
        return self.resolved_impl() != "lax"

    # ------------------------------------------------------------- data plane
    def _ring_fn(self, kind: str, axis_name: str, op: str, shape_key):
        """jit(shard_map(ring kernel)) over the group's 1-D device mesh,
        cached per (kind, op, shape/dtype) to avoid retraces."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        import jax

        from ray_tpu.util.collective import pallas as rk

        key = (kind, axis_name, op, shape_key)
        if key in self._fn_cache:
            return self._fn_cache[key]
        mesh = self.get_mesh(axis_name)
        n = int(np.prod(mesh.devices.shape))

        if kind == "allreduce":
            def fn(x):
                return rk.ring_allreduce(x, axis_name, n=n, op=op)
        elif kind == "quantized_allreduce":
            def fn(x):
                return rk.quantized_ring_allreduce(x, axis_name, n=n, op=op)
        elif kind == "allgather":
            def fn(x):
                return rk.ring_allgather(x, axis_name, n=n)
        elif kind == "reducescatter":
            def fn(x):
                return rk.ring_reduce_scatter(x, axis_name, n=n, op=op)
        else:
            raise ValueError(kind)

        out_specs = P(None, axis_name) if kind == "allgather" \
            else P(axis_name)
        wrapped = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=P(axis_name),
            out_specs=out_specs, check_vma=False))
        self._fn_cache[key] = wrapped
        return wrapped

    def _global_from_local(self, tensor, axis_name: str):
        """Stack the per-rank host tensor into a global device array
        sharded over the group axis (each process contributes its rank's
        slab)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.get_mesh(axis_name)
        local = np.asarray(tensor)
        sharding = NamedSharding(mesh, P(axis_name))
        n_devices = int(np.prod(mesh.devices.shape))
        global_shape = (n_devices * local.shape[0],) + local.shape[1:]
        local_devices = [d for d in mesh.devices.flat
                         if d.process_index == jax.process_index()]
        arrays = [jax.device_put(local, d) for d in local_devices]
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, arrays)

    def device_allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM,
                         axis_name: str = "x", quantized: bool = None):
        """Allreduce a per-rank tensor through the ring kernels (device
        path).  Returns this rank's (identical) copy as numpy."""
        if quantized is None:
            quantized = self._quantized
        local = np.asarray(tensor)
        kind = "quantized_allreduce" if quantized else "allreduce"
        fn = self._ring_fn(kind, axis_name, _RING_OPS[op],
                           (local.shape, str(local.dtype)))
        glob = self._global_from_local(local[None], axis_name)
        out = fn(glob)
        return np.asarray(out.addressable_data(0))[0]

    # Host-level parity ops ride the device ring when viable; XLAGroup's
    # process_allgather parity path stays as the multi-host host fallback.
    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM):
        if self.uses_pallas() and op in _RING_OPS:
            try:
                return self.device_allreduce(tensor, op)
            except Exception:
                pass  # fall back to the host parity path below
        return super().allreduce(tensor, op)

    def destroy(self):
        self._fn_cache.clear()
        super().destroy()
