"""Multi-device JAX bridge self-test.

Proves the cross-backend equivalence claim of DESIGN.md §3: the same Dmap
produces identical local parts under (a) the PythonMPI/NumPy backend and
(b) the JAX mesh sharding — and redistribution through XLA moves values
exactly where PITFALLS says they go.

Each ``check_*`` takes a host field and a world size and runs on the
first ``world`` devices of whatever backend JAX has: ``chip_smoke.py
--chips 4`` calls them on four TPU chips.  ``python -m
repro.launch._jax_selftest`` runs them on 8 forced host devices (the
flag is set before the CPU backend first starts, so importing this
module changes nothing).
"""

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.core as pp
from repro.comm import run_spmd
from repro.core import Dmap
from repro.core.jax_bridge import (
    apply_canonical_layout,
    canonical_permutation,
    expected_redistribution_bytes,
    halo_exchange,
    mesh_for_dmap,
    redistribute,
    scatter_to_mesh,
    undo_canonical_layout,
)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _shards_by_rank(x, world: int) -> dict:
    """Rank -> host copy of its shard; the map's rank r sits on
    ``jax.devices()[r]`` (``mesh_for_dmap``).  Fails unless the shards
    sit on ``world`` distinct devices."""
    devs = jax.devices()
    shards = {devs.index(s.device): np.asarray(s.data)
              for s in x.addressable_shards}
    check(sorted(shards) == list(range(world)),
          f"shards on devices {sorted(shards)}, want {world} distinct")
    return shards


def _assert_shards_equal(x, want: list, world: int) -> None:
    for rank, got in _shards_by_rank(x, world).items():
        check(got.shape == want[rank].shape,
              f"rank {rank}: shard {got.shape} != local {want[rank].shape}")
        check(np.array_equal(got, want[rank]), f"rank {rank}: shard differs")


def check_shards_match_pythonmpi_locals(field: np.ndarray, world: int):
    """Device shard k == PythonMPI rank k's local part, same Dmap."""
    dmap = Dmap([2, world // 2], {}, range(world))
    mesh = mesh_for_dmap(dmap, ("data", "model"))
    x = scatter_to_mesh(field, dmap, mesh, ("data", "model"))

    def body():
        return pp.scatter(field, dmap).local_view_owned()

    _assert_shards_equal(x, run_spmd(body, world), world)


def check_corner_turn(field: np.ndarray, world: int):
    """Z[:, :] = X (row map -> col map) via sharding constraint in jit,
    against the same corner turn through PythonMPI."""
    row = Dmap([world, 1], {}, range(world))
    col = Dmap([1, world], {}, range(world))
    mesh = mesh_for_dmap(row, ("data", "model"))  # grid (world, 1)
    x = scatter_to_mesh(field, row, mesh, ("data", None))
    _shards_by_rank(x, world)

    # col grid over the same devices
    z = jax.jit(lambda v: redistribute(v, NamedSharding(mesh, P(None, "data"))))(x)
    check(np.array_equal(np.asarray(z), field), "corner turn changed values")

    def body():
        X = pp.scatter(field, row)
        Z = pp.zeros(*field.shape, map=col, dtype=field.dtype)
        Z[:, :] = X
        return Z.local

    _assert_shards_equal(z, run_spmd(body, world), world)

    # PITFALLS predicts the off-chip traffic: every block but the diagonal
    pred = expected_redistribution_bytes(field.shape, field.itemsize, row, col)
    want = field.nbytes * (world - 1) // world
    check(pred == want, f"PITFALLS bytes {pred} != {want}")


def check_block_cyclic(field: np.ndarray, world: int, size: int):
    """A block-cyclic row map, canonicalized so XLA's block sharding
    holds each rank's rows: shard k == PythonMPI rank k's local part."""
    n = field.shape[0]
    dist = {"dist": "bc", "size": size}
    dmap = Dmap([world, 1], [dist, "b"], range(world))
    mesh = mesh_for_dmap(Dmap([world, 1], {}, range(world)), ("data", "model"))
    rows = NamedSharding(mesh, P("data", None))
    x = jax.device_put(field, rows)
    y = jax.jit(lambda v: apply_canonical_layout(v, 0, n, world, dist),
                out_shardings=rows)(x)
    perm = canonical_permutation(n, world, dist)
    check(np.array_equal(np.asarray(y), field[perm]), "canonical layout values")

    def body():
        return pp.scatter(field, dmap).local_view_owned()

    _assert_shards_equal(y, run_spmd(body, world), world)
    back = jax.jit(lambda v: undo_canonical_layout(v, 0, n, world, dist),
                   out_shardings=rows)(y)
    check(np.array_equal(np.asarray(back), field), "canonical layout round trip")


def check_halo_exchange(field: np.ndarray, world: int, overlap: int):
    """``halo_exchange`` == PythonMPI ``synch`` on an overlapped row map;
    the last shard's halo is zero (non-periodic)."""
    dmap = Dmap([world, 1], {}, range(world), overlap=[overlap, 0])
    mesh = mesh_for_dmap(Dmap([world, 1], {}, range(world)), ("data", "model"))
    rows = NamedSharding(mesh, P("data", None))
    x = jax.device_put(field, rows)
    out = jax.jit(lambda v: halo_exchange(v, mesh, "data", 0, overlap),
                  out_shardings=rows)(x)

    def body():
        a = pp.scatter(field, dmap)
        pp.synch(a)
        return a.local

    # NumPy: each block of rows followed by its successor's first rows
    per = field.shape[0] // world
    padded = np.concatenate([field, np.zeros_like(field[:overlap])])
    want = np.concatenate([padded[r * per : (r + 1) * per + overlap]
                           for r in range(world)])
    check(np.array_equal(np.asarray(out), want), "halo values")
    locals_mpi = run_spmd(body, world)
    for rank, got in _shards_by_rank(out, world).items():
        want = locals_mpi[rank]
        check(np.array_equal(got[: want.shape[0]], want),
              f"rank {rank}: owned+halo differs from synch")
        check(not got[want.shape[0]:].any(), f"rank {rank}: halo pad not zero")


def check_cyclic_canonicalization(n: int, p: int):
    x = jnp.arange(n, dtype=jnp.float32)
    y = apply_canonical_layout(x, 0, n, p, "c")
    # rank r's cyclic indices are now contiguous
    perm = np.asarray(y, dtype=np.int64)
    per = n // p
    for r in range(p):
        seg = perm[r * per : (r + 1) * per]
        check(all(int(v) % p == r for v in seg), f"rank {r} segment {seg}")
    z = undo_canonical_layout(y, 0, n, p, "c")
    np.testing.assert_array_equal(np.asarray(z), np.asarray(x))


def main():
    # read when the CPU backend starts, i.e. at the first device query
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    world = 8
    check(len(jax.devices()) == world, f"needs {world} host-platform devices")
    field = np.arange(32 * 32, dtype=np.float32).reshape(32, 32)
    check_shards_match_pythonmpi_locals(field, world)
    check_corner_turn(field, world)
    check_block_cyclic(field, world, size=2)
    check_halo_exchange(field, world, overlap=2)
    check_cyclic_canonicalization(24, world)
    print("JAX_BRIDGE_SELFTEST_OK")


if __name__ == "__main__":
    main()
