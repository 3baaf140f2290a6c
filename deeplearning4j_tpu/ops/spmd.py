"""Pallas kernels under a device mesh.

GSPMD cannot partition a Mosaic kernel: a ``pallas_call`` traced inside a
``jit`` whose operands are sharded over more than one device fails at
lowering with "Mosaic kernels cannot be automatically partitioned. Please
wrap the call in a shard_map". Every default-dispatch kernel here is
independent along the batch axis, so the wrap is mechanical — but the mesh
is known to the caller that shards the batch (``ParallelTrainer``,
``BucketedForward(mesh=)``), not to the kernel, and GSPMD shardings do not
exist yet at trace time.

So the callers DECLARE the mesh for the duration of the trace
(:func:`kernel_mesh`, entered inside the function they jit, whose Python
body only runs while tracing), and the kernel entry points run their
``pallas_call`` through :func:`per_batch_shard`.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import PartitionSpec as P

_mesh = contextvars.ContextVar("dl4j_tpu_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Declare that traces inside this block shard their batch over
    ``mesh``'s ``data`` axis (None or a one-device mesh: no-op)."""
    token = _mesh.set(mesh)
    try:
        yield
    finally:
        _mesh.reset(token)


def _spec(axis):
    return P() if axis is None else P(*([None] * axis), "data")


def per_batch_shard(fn, args, arg_axes, out_axes):
    """``fn(*args)`` — under a declared multi-device mesh, once per batch
    shard through ``jax.shard_map``.

    ``arg_axes`` / ``out_axes`` give, per array, the index of its batch
    axis (None: replicated, e.g. weights). The map is manual over the WHOLE
    mesh with only ``data`` named in the specs: the other axes see
    replicated operands, so a tensor-parallel caller recomputes the kernel
    per model shard rather than failing. Already inside a ``shard_map``
    (ring attention, pipeline stages) the call is manual as it stands."""
    mesh = _mesh.get()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return fn(*args)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(_spec(a) for a in arg_axes),
        out_specs=tuple(_spec(a) for a in out_axes),
        check_vma=False)(*args)
