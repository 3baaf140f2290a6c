"""Device-mesh helpers.

Reference analog: the device-topology assumptions inside ParallelWrapper
(/root/reference/deeplearning4j-scaleout/deeplearning4j-scaleout-
parallelwrapper/.../ParallelWrapper.java — one replica per CUDA device) and
the Spark cluster layout of the TrainingMasters. TPU-native replacement: a
``jax.sharding.Mesh`` with named axes

    data  — data parallelism (replica axis; per-step psum of grads rides ICI)
    model — tensor parallelism (weight shards; collectives inserted by XLA)
    seq   — sequence/context parallelism for long sequences
    stage — pipeline parallelism (GPipe microbatch schedule; parallel/pipeline.py)

Multi-host: pass all ``jax.devices()`` from a jax.distributed-initialized
process set; the same named-axis code then spans hosts with DCN-aware
collective lowering — the reference's Aeron/Spark tier collapses into this.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named mesh shape; -1 on the data axis = use all remaining devices."""

    data: int = -1
    model: int = 1
    seq: int = 1
    stage: int = 1

    def resolve(self, n_devices):
        d = self.data
        if d == -1:
            d = n_devices // (self.model * self.seq * self.stage)
        assert d * self.model * self.seq * self.stage == n_devices, \
            (f"mesh {d}x{self.model}x{self.seq}x{self.stage} != "
             f"{n_devices} devices")
        return d, self.model, self.seq, self.stage


def make_mesh(spec: MeshSpec | None = None, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    spec = spec or MeshSpec()
    d, m, s, st = spec.resolve(len(devices))
    arr = np.asarray(devices).reshape(d, m, s, st)
    return Mesh(arr, axis_names=("data", "model", "seq", "stage"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharding over the data axis."""
    return NamedSharding(mesh, P("data"))


def superbatch_sharded(mesh: Mesh) -> NamedSharding:
    """Sharding for stacked ``[K, B, ...]`` super-batches (nn/fused.py):
    the scan axis K stays whole on every device, the batch axis shards
    over 'data' — each replica scans its own slice of all K steps."""
    return NamedSharding(mesh, P(None, "data"))


def zero1_sharding(mesh: Mesh, sharding: NamedSharding, leaf, axis="data"):
    """Extend a param sharding with ``axis`` for the ZeRO (cross-replica
    sharded weight update, Xu et al. 2020 arxiv 2004.13336) copy of that
    leaf — optimizer-state moments, or the params themselves in the FSDP
    tier. Derived FROM the param sharding, so a tensor-parallel leaf
    keeps its 'model' axes and only gains 'data' on top (the moments of a
    column-sharded W are never resharded against their param).

    The FIRST dim whose per-device size divides by the axis size takes
    the extension (dim 0 in the common case; an embedding-table moment
    like [4097, 512] on an 8-way axis falls through to P(None, 'data')
    instead of replicating). Leaves with no divisible dim keep the param
    sharding unchanged — correctness is unaffected either way; they just
    stay replicated over ``axis``.
    """
    ax_n = mesh.shape[axis]
    if ax_n == 1 or jnp.ndim(leaf) == 0:
        return sharding
    spec = list(sharding.spec) if sharding.spec else []
    spec += [None] * (jnp.ndim(leaf) - len(spec))
    flat = [a for e in spec for a in
            (e if isinstance(e, tuple) else () if e is None else (e,))]
    if axis in flat:
        return sharding
    for dim, entry in enumerate(spec):
        axes = (entry if isinstance(entry, tuple)
                else () if entry is None else (entry,))
        shard_n = int(np.prod([mesh.shape[a] for a in axes], dtype=int))
        if (leaf.shape[dim] // shard_n) % ax_n != 0:
            continue
        merged = tuple(axes) + (axis,)
        # normalize 1-tuples to the bare name: P('data') and
        # P(('data',)) are the same placement, and the bare form is what
        # tests/specs compare
        spec[dim] = merged[0] if len(merged) == 1 else merged
        return NamedSharding(mesh, P(*spec))
    return sharding


def slab_sharding(mesh: Mesh, sharding: NamedSharding) -> NamedSharding:
    """Sharding for a ``[L, ...block]`` stacked slab built from one block
    leaf's sharding: the block spec shifts one dim right and the leading
    stack axis stays UNSHARDED — a ``lax.scan`` over the slab slices that
    axis, so it must be whole on every device while the within-block dims
    keep their 1/N layout (the ZeRO-3 streamed-gather step,
    data_parallel._streamed_loss; the stacked-trunk discipline of
    parallel/pipeline.py, where the leading axis shards over 'stage'
    instead because there the BLOCKS are distributed, not scanned)."""
    spec = tuple(sharding.spec) if sharding.spec else ()
    return NamedSharding(mesh, P(None, *spec))


def opt_shardings_like(opt_state, params, p_shards, replicated_sharding):
    """Sharding pytree for an updater-state tree: every entry structured
    like the params tree (Adam m/v, Nesterov momenta, ...) takes the
    per-leaf ``p_shards``; anything else (bare scalars, empty states)
    replicates. Shared by ParallelTrainer and ComposedParallelLM so the
    ZeRO discipline is one definition, not two."""
    p_struct = jax.tree_util.tree_structure(params)
    # a params-shaped state (Nesterovs/AdaGrad/RmsProp momenta) takes the
    # per-leaf shardings WHOLE — checked before the dict fan-out below,
    # because a ComputationGraph's params tree is ITSELF a dict (keyed by
    # vertex): fanning such a state out per-vertex would compare each
    # vertex sub-dict against the full params structure, fail, and
    # silently replicate every moment leaf
    if jax.tree_util.tree_structure(opt_state) == p_struct:
        return p_shards

    def per_entry(sub):
        if jax.tree_util.tree_structure(sub) == p_struct:
            return p_shards
        return jax.tree_util.tree_map(lambda _: replicated_sharding, sub)

    # a dict wrapper holding several params-shaped entries (Adam m/v)
    if isinstance(opt_state, dict):
        return {k: per_entry(v) for k, v in opt_state.items()}
    return per_entry(opt_state)


def shard_batch(mesh: Mesh, batch):
    """Place a host batch sharded over the data axis."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, data_sharded(mesh)), batch)


def ensure_sharded(a, sharding):
    """``device_put`` to ``sharding`` — skipped when ``a`` is already a
    device array with exactly that sharding: steady-state training loops
    feed already-sharded arrays and pay zero placement dispatches. Host
    data goes from the host straight to its shards (``jnp.asarray`` first
    would stage the WHOLE array on the first device)."""
    if isinstance(a, jax.Array):
        return a if a.sharding == sharding else jax.device_put(a, sharding)
    return jax.device_put(np.asarray(a), sharding)


def ensure_data_sharded(mesh: Mesh, a):
    """`ensure_sharded` onto the data axis of ``mesh``."""
    return ensure_sharded(a, data_sharded(mesh))
