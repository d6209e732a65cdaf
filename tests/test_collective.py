"""Collective layer tests: shm (CPU hub) and xla (jax.distributed) backends.
(Reference model: `python/ray/util/collective/tests/` single-node tier.)"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util.collective.types import ReduceOp


def _jax_cpu_multiprocess_supported() -> bool:
    """jax < 0.5 raises INVALID_ARGUMENT on any cross-process CPU
    computation (no gloo transport); the jax_num_cpu_devices config option
    landed in the same release line and is a cheap capability probe."""
    import jax

    return hasattr(jax.config, "jax_num_cpu_devices")


@ray_tpu.remote
class CollectiveWorker:
    """Test actor implementing the _init_collective protocol used by
    create_collective_group."""

    def _init_collective(self, world_size, rank, backend, group_name):
        from ray_tpu.util import collective as col

        col.init_collective_group(world_size, rank, backend=backend,
                                  group_name=group_name,
                                  **({"platform": "cpu"}
                                     if backend == "xla" else {}))
        self.rank = rank
        return True

    def allreduce(self, value, group_name="default"):
        from ray_tpu.util import collective as col

        return col.allreduce(np.array(value, dtype=np.float32),
                             group_name=group_name)

    def allgather(self, value, group_name="default"):
        from ray_tpu.util import collective as col

        return col.allgather(np.array(value, dtype=np.float32),
                             group_name=group_name)

    def broadcast(self, value, src, group_name="default"):
        from ray_tpu.util import collective as col

        return col.broadcast(np.array(value, dtype=np.float32), src,
                             group_name=group_name)

    def reducescatter(self, value, group_name="default"):
        from ray_tpu.util import collective as col

        return col.reducescatter(np.array(value, dtype=np.float32),
                                 group_name=group_name)

    def p2p(self, peer, send_first, group_name="default"):
        from ray_tpu.util import collective as col

        if send_first:
            col.send(np.full(4, float(self.rank)), peer,
                     group_name=group_name)
            return None
        return col.recv(peer, group_name=group_name)


def _make_group(backend, group_name, n=2):
    from ray_tpu.util import collective as col

    actors = [CollectiveWorker.remote() for _ in range(n)]
    col.create_collective_group(actors, n, list(range(n)), backend=backend,
                                group_name=group_name)
    return actors


class TestSHMBackend:
    def test_allreduce(self, ray_start_regular):
        actors = _make_group("shm", "g1")
        out = ray_tpu.get([a.allreduce.remote([1.0, 2.0], "g1")
                           for a in actors], timeout=120)
        for o in out:
            np.testing.assert_array_equal(o, [2.0, 4.0])

    def test_allgather_and_broadcast(self, ray_start_regular):
        actors = _make_group("shm", "g2")
        ag = ray_tpu.get([actors[i].allgather.remote([float(i)], "g2")
                          for i in range(2)], timeout=120)
        for per_rank in ag:
            np.testing.assert_array_equal(per_rank[0], [0.0])
            np.testing.assert_array_equal(per_rank[1], [1.0])
        bc = ray_tpu.get([actors[i].broadcast.remote([float(i + 10)], 0, "g2")
                          for i in range(2)], timeout=120)
        for o in bc:
            np.testing.assert_array_equal(o, [10.0])

    def test_reducescatter(self, ray_start_regular):
        actors = _make_group("shm", "g3")
        out = ray_tpu.get([
            actors[i].reducescatter.remote([1.0, 2.0, 3.0, 4.0], "g3")
            for i in range(2)], timeout=120)
        np.testing.assert_array_equal(out[0], [2.0, 4.0])
        np.testing.assert_array_equal(out[1], [6.0, 8.0])

    def test_send_recv(self, ray_start_regular):
        actors = _make_group("shm", "g4")
        recv_ref = actors[1].p2p.remote(0, False, "g4")
        ray_tpu.get(actors[0].p2p.remote(1, True, "g4"), timeout=120)
        np.testing.assert_array_equal(ray_tpu.get(recv_ref, timeout=120),
                                      np.zeros(4))


@pytest.mark.skipif(
    not _jax_cpu_multiprocess_supported(),
    reason="installed jax lacks multiprocess CPU collectives (gloo)")
class TestXLABackend:
    def test_allreduce_multiprocess(self, ray_start_regular):
        """Two actor processes rendezvous via jax.distributed (gloo CPU) —
        structurally identical to the multi-host TPU/ICI path."""
        actors = _make_group("xla", "jx1")
        out = ray_tpu.get([actors[i].allreduce.remote([float(i + 1)] * 3,
                                                      "jx1")
                           for i in range(2)], timeout=180)
        for o in out:
            np.testing.assert_array_equal(o, [3.0, 3.0, 3.0])

    def test_mesh_collective_in_jit(self, ray_start_regular):
        """In-jit psum over the group mesh — the actual ICI data path."""

        @ray_tpu.remote
        class MeshWorker:
            def _init_collective(self, world_size, rank, backend, group_name):
                from ray_tpu.util import collective as col

                col.init_collective_group(world_size, rank, backend="xla",
                                          group_name=group_name,
                                          platform="cpu")
                return True

            def jit_psum(self, group_name):
                import jax
                import jax.numpy as jnp
                from jax import shard_map
                from jax.sharding import NamedSharding, PartitionSpec as P

                from ray_tpu.util import collective as col

                mesh = col.get_group_mesh(group_name, axis_name="x")
                rank = col.get_rank(group_name)

                # Each process contributes its local shard of a global array.
                local = jnp.full((2, 4), float(rank + 1))
                garr = jax.make_array_from_single_device_arrays(
                    (2 * mesh.devices.size, 4),
                    NamedSharding(mesh, P("x", None)),
                    [jax.device_put(local, d) for d in jax.local_devices()])

                f = jax.jit(shard_map(
                    lambda x: jax.lax.psum(x, "x"),
                    mesh=mesh, in_specs=P("x", None), out_specs=P("x", None)))
                out = f(garr)
                # psum sums over every device: L devices/process, values
                # (rank+1) => expected = L*1 + L*2.
                expected = (jax.device_count() // 2) * 3.0
                return (np.asarray(out.addressable_shards[0].data).tolist(),
                        expected)

        from ray_tpu.util import collective as col

        actors = [MeshWorker.remote() for _ in range(2)]
        col.create_collective_group(actors, 2, [0, 1], backend="xla",
                                    group_name="jx2")
        out = ray_tpu.get([a.jit_psum.remote("jx2") for a in actors],
                          timeout=180)
        for shard, expected in out:
            assert np.allclose(shard, expected)
