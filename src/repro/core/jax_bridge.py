"""Dmap -> JAX lowering: PGAS maps as TPU shardings (DESIGN.md §3, §4).

The paper's transport (files on a shared filesystem) has no TPU analogue;
the *index algebra* does.  This module maps the Dmap construct onto JAX's
mesh/sharding machinery so that the same map that drives PythonMPI
messages on CPU drives XLA collectives on TPU:

* ``dmap_to_partition_spec``  — block maps become ``PartitionSpec`` axes.
* ``canonical_permutation``   — cyclic/block-cyclic maps are canonicalized
  by an index permutation that makes each rank's owned indices contiguous
  (the HPF trick), after which block sharding applies.  XLA has no cyclic
  sharding; this is the documented semantic adaptation.
* ``redistribute``            — the paper's ``Z[:, :] = X`` inside jit:
  a sharding constraint change, which XLA lowers to all-to-all /
  collective-permute on ICI.  PITFALLS stays in the loop as the *oracle*:
  ``expected_redistribution_bytes`` predicts the off-chip traffic, and the
  dry-run checks the compiled HLO moves the same order of bytes.
* ``halo_exchange``           — the overlap feature as a shard_map
  ``ppermute`` (the TPU idiom for ghost cells).

Differences vs. the paper, by design (DESIGN.md §9):
  - XLA block sharding pads the *last* shard when ``n % p != 0``; pPython's
    enhanced block deals remainders from rank 0.  Equal when ``p | n`` —
    which the bridge asserts for distributed dims — so production configs
    are unaffected; PythonMPI remains the reference for ragged shapes.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .dmap import Dmap
from .pitfalls import falls_list_indices, falls_list_intersect

__all__ = [
    "dmap_to_partition_spec",
    "sharding_for",
    "mesh_for_dmap",
    "canonical_permutation",
    "apply_canonical_layout",
    "undo_canonical_layout",
    "redistribute",
    "halo_exchange",
    "expected_redistribution_bytes",
    "scatter_to_mesh",
]


def dmap_to_partition_spec(
    dmap: Dmap,
    dim_axes: Sequence[str | None],
) -> P:
    """PartitionSpec for a Dmap given the mesh axis bound to each array dim.

    ``dim_axes[d]`` names the mesh axis sharding dim ``d`` (None =
    replicated; grid must be 1 there).  Cyclic/block-cyclic dims must be
    canonicalized first (``apply_canonical_layout``).
    """
    if len(dim_axes) != dmap.ndim:
        raise ValueError(f"dim_axes has {len(dim_axes)} entries for {dmap.ndim}-D map")
    spec = []
    for d, axis in enumerate(dim_axes):
        g = dmap.grid[d]
        if axis is None:
            if g != 1:
                raise ValueError(
                    f"dim {d} has grid {g} but no mesh axis bound to it"
                )
            spec.append(None)
        else:
            spec.append(axis)
    return P(*spec)


def mesh_for_dmap(dmap: Dmap, axis_names: Sequence[str] | None = None) -> Mesh:
    """Build a device mesh shaped like the map's processor grid.

    Uses the first ``prod(grid)`` local devices in proclist order, honoring
    the map's row/col ``order`` — pMatlab's column-major grids produce the
    transposed device layout, exactly as the paper's ``order`` keyword.
    """
    if axis_names is None:
        axis_names = tuple(f"g{d}" for d in range(dmap.ndim))
    devs = np.asarray(jax.devices())[list(dmap.proclist)]
    order = "C" if dmap.order == "row" else "F"
    arr = devs.reshape(dmap.grid, order=order)
    return Mesh(arr, tuple(axis_names))


def sharding_for(
    dmap: Dmap, mesh: Mesh, dim_axes: Sequence[str | None]
) -> NamedSharding:
    return NamedSharding(mesh, dmap_to_partition_spec(dmap, dim_axes))


# ---------------------------------------------------------------------------
# Cyclic canonicalization (HPF-style layout permutation)
# ---------------------------------------------------------------------------


def canonical_permutation(n: int, p: int, dist) -> np.ndarray:
    """Permutation ``perm`` with ``x[perm]`` rank-contiguous for ``dist``.

    Concatenates each rank's owned indices in rank order; for block dists
    this is the identity.  After the permutation the axis is block
    distributed (fair-share), so standard XLA sharding applies.
    """
    from .pitfalls import dist_falls

    parts = [falls_list_indices(dist_falls(n, p, r, dist)) for r in range(p)]
    perm = np.concatenate([x for x in parts if len(x)])
    if len(perm) != n:
        raise ValueError("distribution does not cover the axis")
    return perm


def apply_canonical_layout(x: jax.Array, dim: int, n: int, p: int, dist) -> jax.Array:
    perm = jnp.asarray(canonical_permutation(n, p, dist))
    return jnp.take(x, perm, axis=dim)


def undo_canonical_layout(x: jax.Array, dim: int, n: int, p: int, dist) -> jax.Array:
    perm = canonical_permutation(n, p, dist)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    return jnp.take(x, jnp.asarray(inv), axis=dim)


# ---------------------------------------------------------------------------
# Redistribution (the paper's Z[:, :] = X) inside jit
# ---------------------------------------------------------------------------


def redistribute(x: jax.Array, dst: NamedSharding | P, mesh: Mesh | None = None):
    """Resharding constraint: XLA emits the collective schedule that the
    PITFALLS algebra computes explicitly on the CPU backend."""
    if isinstance(dst, P):
        if mesh is None:
            raise ValueError("mesh required when dst is a PartitionSpec")
        dst = NamedSharding(mesh, dst)
    return jax.lax.with_sharding_constraint(x, dst)


def expected_redistribution_bytes(
    shape: Sequence[int],
    itemsize: int,
    src: Dmap,
    dst: Dmap,
) -> int:
    """PITFALLS-predicted off-chip traffic for ``dst[...] = src``.

    Sums element counts over all (sender, receiver) pairs with
    ``sender != receiver``; the product over dims of per-dim intersection
    sizes is the pair's block volume.  This is the oracle the dry-run
    roofline compares against the HLO's collective operand bytes.
    """
    shape = tuple(shape)
    total = 0
    for s_rank in src.proclist:
        for d_rank in dst.proclist:
            if s_rank == d_rank:
                continue
            vol = 1
            for d in range(len(shape)):
                a = src.dim_falls(shape, d, s_rank)
                b = dst.dim_falls(shape, d, d_rank)
                inter = falls_list_intersect(a, b)
                cnt = sum(f.n * f.seg_len for f in inter)
                if cnt == 0:
                    vol = 0
                    break
                vol *= cnt
            total += vol * itemsize
    return total


# ---------------------------------------------------------------------------
# Halo exchange (the paper's overlap) as a TPU collective
# ---------------------------------------------------------------------------


def halo_exchange(x: jax.Array, mesh: Mesh, axis: str, dim: int, overlap: int):
    """Append each shard's successor-facing halo along ``dim``.

    Equivalent of ``synch`` (paper §III.E) for block maps: every shard
    receives the first ``overlap`` slices of its successor shard via
    ``ppermute`` and concatenates them past its owned end.  The last shard
    pads with zeros (non-periodic, like pPython's edge ranks).

    Works inside jit; input must be sharded over ``axis`` along ``dim``.
    """
    n_shards = mesh.shape[axis]
    in_spec = [None] * x.ndim
    in_spec[dim] = axis
    spec = P(*in_spec)

    def body(xl):
        lead = jax.lax.slice_in_dim(xl, 0, overlap, axis=dim)
        perm = [(i, i - 1) for i in range(1, n_shards)]
        halo = jax.lax.ppermute(lead, axis, perm)  # shard i gets shard i+1's lead
        return jnp.concatenate([xl, halo], axis=dim)

    return jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)(x)


def scatter_to_mesh(
    arr: np.ndarray, dmap: Dmap, mesh: Mesh, dim_axes: Sequence[str | None]
) -> jax.Array:
    """Place a host array on the mesh under the map's sharding."""
    return jax.device_put(arr, sharding_for(dmap, mesh, dim_axes))
