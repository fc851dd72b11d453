"""Halo exchange over the device mesh: ``ppermute`` in place of MPI.

TPU-native re-design of the reference's ghost-layer machinery
(``exchange_halos_2d``: nonblocking Isend/Irecv ×4 + Waitall,
``stage2-mpi/poisson_mpi_decomp.cpp:241-347``; stage4's GPU variant stages
edges D2H, runs blocking ``MPI_Sendrecv``, copies H2D and memsets physical
boundaries, ``stage4-mpi+cuda/poisson_mpi_cuda_f.cu:331-500``):

- each shift is one ``lax.ppermute`` along a mesh axis, resident on ICI —
  no host staging, no per-direction tags, no explicit waits;
- ``MPI_PROC_NULL`` edges (``stage2:…cpp:249-252``) need no sentinel:
  a device absent from the permutation's source list receives *zeros*,
  which is exactly the homogeneous Dirichlet boundary value;
- stage4's ``cudaMemcpy2D`` strided-column staging has no analog — both
  axes slice contiguously out of VMEM/HBM-resident shards.

As in the reference, corners are not exchanged diagonally; the 5-point
stencil never reads them (SURVEY §2.4). Exchanged slices span the full
halo-inclusive extent, matching the reference's length-(local+2) messages.
"""

from __future__ import annotations

from jax import lax

from poisson_tpu.parallel.mesh import X_AXIS, Y_AXIS


def _shift_down(u_slice, axis_name: str, size: int):
    """Value from mesh coordinate c−1 (zeros at c=0)."""
    return lax.ppermute(
        u_slice, axis_name, [(i, i + 1) for i in range(size - 1)]
    )


def _shift_up(u_slice, axis_name: str, size: int):
    """Value from mesh coordinate c+1 (zeros at c=size−1)."""
    return lax.ppermute(
        u_slice, axis_name, [(i + 1, i) for i in range(size - 1)]
    )


def exchange_halos(u, px_size: int, py_size: int, depth: int = 1,
                   owned=None):
    """Refresh the ``depth`` halo lines on each side of a local block's
    owned lines from the neighbours' first and last ``depth`` owned lines.

    Must be called inside ``shard_map`` over a mesh with axes (x, y).
    ``owned`` is ((r0, r1), (c0, c1)), the block's owned rows and columns;
    by default all but a ``depth``-line ring, so the default refreshes the
    width-1 ring of a (m+2, n+2) block — called once per PCG iteration on
    the search direction p, exactly like the reference
    (``stage2:…cpp:404``). The sharded V-cycle's strip-kernel levels
    (``parallel.mg_sharded``) refresh both lines of a 2-line ring, or
    only its inner one; the CA solve (``parallel.pallas_ca_sharded``) the
    2-line ring around an aligned canvas's owned band.

    One ``ppermute`` per direction, 4 total per call. Rows first, then
    columns over the whole height: the halo rows just received ride along
    in the column slices, as in the reference's halo-inclusive messages,
    so the corners carry the diagonal neighbours' values; past the mesh's
    edges the halo reads zeros.
    """
    if owned is None:
        owned = tuple((depth, n - depth) for n in u.shape)

    def refresh(u, axis: int, mesh_axis: str, size: int):
        lo, hi = owned[axis]

        def send(shift, start):
            lines = lax.slice_in_dim(u, start, start + depth, axis=axis)
            if depth > 1:
                return shift(lines, mesh_axis, size)
            # One line travels as a vector: XLA fuses its reshape into the
            # update, where an (m, 1) column would take a relayout copy.
            return lax.expand_dims(
                shift(lax.squeeze(lines, (axis,)), mesh_axis, size), (axis,))

        down, up = send(_shift_down, hi - depth), send(_shift_up, lo)
        u = lax.dynamic_update_slice_in_dim(u, down, lo - depth, axis)
        return lax.dynamic_update_slice_in_dim(u, up, hi, axis)

    u = refresh(u, 0, X_AXIS, px_size)
    return refresh(u, 1, Y_AXIS, py_size)
