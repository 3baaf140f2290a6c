"""Pipeline parallelism for ARBITRARY layer stacks (heterogeneous stages).

Reference analog: ParallelWrapper.java:58 wraps *any* Model — the
reference's scale-out tiers never restricted which architectures they
apply to. ``parallel/pipeline.py`` pipelines the homogeneous stacked
transformer trunk; this module generalizes the same GPipe schedule to any
``MultiLayerNetwork`` configuration (VGG16, the char-RNN, an MLP, and —
via the ResidualBottleneck composite layer — ResNet50, VERDICT r3 #5 /
r4 #3), split into ``n_stages`` contiguous layer groups — and, via
``PipelinedGraph`` at the bottom of the module, to any single-input /
single-output ``ComputationGraph`` DAG (the real 141-vertex ResNet50
graph included).

TPU-first design: the obstacle to heterogeneous stages under SPMD is that
``shard_map`` traces ONE program for all devices while each stage owns a
DIFFERENT param structure and layer code. Both are bridged with padding +
static dispatch:

* Params: each stage's param pytree is raveled into one flat f32 vector,
  zero-padded to the longest stage, and stacked [S, Lmax] sharded
  ``P('stage')`` — every device holds exactly its own stage's weights
  (real weight sharding, memory scales down with S; the pad waste is
  bounded by stage imbalance, not by the union of structures). Inside the
  kernel each stage unflattens its slab with its OWN static spec inside a
  ``lax.switch`` branch — the switch runs on ``axis_index('stage')``, so
  each device executes only its stage's branch.
* Mutable layer state (BatchNorm running statistics) rides the SAME
  mechanism: a per-stage flat state slab [S, Smax] sharded ``P('stage')``
  — each stage already owns its layers, so their running stats are
  stage-local by construction. The slab is threaded through the tick
  scan's carry and updated only on active ticks, so microbatches update
  the stats sequentially in microbatch order — exactly the update
  sequence a sequential per-microbatch run produces. BN's train-mode
  forward normalizes with the CURRENT microbatch's statistics (standard
  GPipe semantics — and the reference's: each ParallelWrapper worker
  normalizes with its own local batch statistics). With a 'data' mesh
  axis the stats are additionally pmean'd over it after the schedule
  (ghost batch norm, per-shard normalization).
* Dropout / weight noise: a per-step key is folded with the microbatch
  index, then the stage branch REPLICATES MultiLayerNetwork.apply_fn's
  exact key-split chain over all layers (splits are a few scalar ops —
  negligible), consuming only its own layers' subkeys. Masks are
  therefore bit-identical to a sequential run of the same microbatch
  with the same per-microbatch key — the loss-pin tests assert this.
* Activations: inter-stage tensors differ in shape (conv pyramids,
  conv->FC transitions), so the rotating GPipe buffer carries a flat
  [mb, Amax] activation padded to the largest boundary; each branch
  unflattens by its static input shape and re-flattens its output.
* Schedule: the same tick loop as ``pipeline.gpipe_schedule`` — at tick t
  stage s runs microbatch t-s, one ``ppermute`` hop per tick; backward is
  derived by AD through scan+ppermute+switch (the transpose of a switch
  is the switch of the transposes).
* The output layer's FORWARD runs in the last stage; the loss (and the
  L1/L2 penalties, reference calcL1/calcL2 semantics) are computed outside
  the pipelined region from the collected predictions, so the pipeline
  loss is bit-identical to ``MultiLayerNetwork.loss_fn`` on the same
  params.

Both schedules take BN state and dropout: GPipe threads the state slab
through its tick scan; 1F1B threads it through the shared combined-tick
engine's ``state0`` path (pipeline.run_combined_ticks), whose backward
half recomputes stage forwards — exact because BN's train forward is
state-independent and the dropout keys are deterministic per-microbatch
operands (the recompute redraws identical masks, the jax.checkpoint
contract). Sequence masks ride along as a per-microbatch [M, mb, T]
operand handed to mask-aware layers and the output loss (the
MultiLayerNetwork mask contract), so padded RNN batches stage too. The
one remaining constraint, asserted at build: no aux-loss layers (MoE —
their load-balancing term lives in the activation path, not the state
path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from deeplearning4j_tpu.parallel import mesh as _mesh
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers import base as _lbase


# the SAME mask-awareness predicate MultiLayerNetwork uses — the
# loss-pin equivalence depends on both paths masking identical layers
from deeplearning4j_tpu.nn.multilayer import _accepts_mask  # noqa: E402


def _type_shape(it, mb):
    """Concrete activation shape for a batch of ``mb`` at an InputType."""
    if isinstance(it, _inputs.ConvolutionalType):
        return (mb, it.height, it.width, it.channels)
    if isinstance(it, _inputs.RecurrentType):
        assert it.timesteps is not None, \
            "pipelined RNN stacks need a static sequence length"
        return (mb, it.timesteps, it.size)
    return (mb, it.size)


def _flatten_tree(tree):
    """tree -> (flat f32 vector, unflatten(vec)->tree)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    sizes = [int(np.prod(s)) for s in shapes]
    dtypes = [l.dtype for l in leaves]

    def unflatten(vec):
        out, off = [], 0
        for sh, sz, dt in zip(shapes, sizes, dtypes):
            out.append(vec[off:off + sz].reshape(sh).astype(dt))
            off += sz
        return jax.tree_util.tree_unflatten(treedef, out)

    flat = (jnp.concatenate([l.astype(jnp.float32).ravel() for l in leaves])
            if leaves else jnp.zeros((0,), jnp.float32))
    return flat, unflatten, sum(sizes)


def _greedy_balance(counts, n_stages):
    """Contiguous group bounds over per-item param counts (greedy: close
    each group once it reaches the ideal share). Shared by the layer and
    vertex balancers — returns [(start, end)] index pairs."""
    total = sum(counts) or 1
    ideal = total / n_stages
    bounds, acc = [], 0.0
    for i, c in enumerate(counts):
        acc += c
        remaining = len(counts) - i - 1
        rem_stages = n_stages - len(bounds) - 1
        if acc >= ideal and rem_stages > 0 and remaining >= rem_stages:
            bounds.append(i + 1)
            acc = 0.0
    while len(bounds) < n_stages - 1:  # degenerate: force non-empty stages
        cand = [i for i in range(1, len(counts)) if i not in bounds]
        bounds.append(cand[0])
        bounds.sort()
    out, prev = [], 0
    for b in bounds + [len(counts)]:
        out.append((prev, b))
        prev = b
    return out


def balance_stages(conf, n_stages):
    """Contiguous stage boundaries balancing per-stage param counts."""
    assert n_stages <= len(conf.layers), \
        f"{n_stages} stages need at least that many layers " \
        f"(got {len(conf.layers)})"
    counts = []
    key = jax.random.PRNGKey(0)
    for layer, it in zip(conf.layers, conf.layer_input_types()[0]):
        # eval_shape: param COUNTS without allocating a second full model
        p = jax.eval_shape(lambda k, _l=layer, _it=it: _l.init(k, _it), key)
        counts.append(sum(int(np.prod(l.shape))
                          for l in jax.tree_util.tree_leaves(p)))
    return [list(range(a, b))
            for a, b in _greedy_balance(counts, n_stages)]


class PipelinedNetwork:
    """GPipe-pipeline any MultiLayerConfiguration over a mesh 'stage' axis.

    ``stage_layers``: optional list of contiguous layer-index groups (one
    per stage, in order); defaults to a param-count-balanced split.
    Batch B must divide into ``n_microbatches``; composes with a 'data'
    mesh axis for batch sharding within each microbatch.
    """

    def __init__(self, conf, mesh: Mesh, *, n_microbatches=4,
                 stage_layers=None, updater=None, seed=None,
                 schedule="gpipe"):
        assert "stage" in mesh.axis_names, "mesh needs a 'stage' axis"
        assert schedule in ("gpipe", "1f1b"), schedule
        self.conf = conf
        self.mesh = mesh
        self.schedule = schedule
        self.n_micro = n_microbatches
        self.n_stages = mesh.shape["stage"]
        self.updater = updater or conf.updater
        self.seed = conf.seed if seed is None else seed
        self.groups = (stage_layers if stage_layers is not None
                       else balance_stages(conf, self.n_stages))
        assert len(self.groups) == self.n_stages
        flat_idx = [i for g in self.groups for i in g]
        assert flat_idx == list(range(len(conf.layers))), \
            "stage_layers must be contiguous groups covering every layer"
        self.layer_inputs, self.output_type = conf.layer_input_types()
        self._mask_aware = [_accepts_mask(layer) for layer in conf.layers]
        assert conf.gradient_normalization in (None, "none"), \
            "PipelinedNetwork does not apply gradient normalization; " \
            "clip on the sequential MultiLayerNetwork path"
        assert not conf.ties, \
            "a tied parameter is read by two layers, which may lie on " \
            "two stages; not stageable"
        _lbase.refuse_loss_mask_layers(conf.layers, "PipelinedNetwork")
        assert not hasattr(conf.layers[-1], "loss_from_features"), \
            "feature-loss heads (CenterLossOutputLayer) need the " \
            "pre-head activations MultiLayerNetwork.loss_fn threads " \
            "specially; not stageable"
        for layer in conf.layers:
            assert not hasattr(layer, "aux_loss_weight"), \
                f"{type(layer).__name__} emits an aux loss; aux-loss " \
                "layers (MoE) are not supported inside pipelined stages " \
                "(use parallel/moe.py's expert-parallel tier)"
        # both schedules thread BN state + per-microbatch dropout keys
        self.use_rng = any(
            getattr(layer, "dropout", 0.0) not in (0.0, None)
            or getattr(layer, "weight_noise", None) is not None
            for layer in conf.layers)
        self.params = None
        self.state = None
        self.opt_state = None
        self._step_fn = None
        self.iteration = 0
        self.listeners = []
        self._rng = jax.random.PRNGKey(self.seed)

    def add_listener(self, listener):
        """TrainingListener fired after every step (reference:
        ParallelWrapper.setListeners). Firing syncs the loss to host —
        attach only when the telemetry is wanted. (Param-stat listeners
        see the packed stage slab, whose zero padding dilutes per-param
        statistics; num_params() reports the true unpadded count.)"""
        self.listeners.append(listener)
        return self

    def num_params(self):
        """True (unpadded) parameter count — the packed [S, Lmax] slab
        carries zero padding up to the largest stage."""
        return self._n_params

    # -- packing ---------------------------------------------------------
    def _init_trees(self, rng):
        params = []
        for layer, it in zip(self.conf.layers, self.layer_inputs):
            rng, sub = jax.random.split(rng)
            params.append(layer.init(sub, it))
        return params

    def _pack(self, layer_params):
        """Per-layer param list -> ([S, Lmax] f32 stage buffer, specs)."""
        flats, unflats, sizes = [], [], []
        for g in self.groups:
            f, u, n = _flatten_tree([layer_params[i] for i in g])
            flats.append(f)
            unflats.append(u)
            sizes.append(n)
        lmax = max(max(sizes), 1)
        buf = jnp.stack([jnp.pad(f, (0, lmax - f.shape[0])) for f in flats])
        self._unflats = unflats
        self._n_params = sum(sizes)
        return buf

    def _pack_state(self, layer_states):
        """Per-layer state list -> [S, Smax] f32 stage state slab."""
        flats, unflats, sizes = [], [], []
        for g in self.groups:
            f, u, n = _flatten_tree([layer_states[i] for i in g])
            flats.append(f)
            unflats.append(u)
            sizes.append(n)
        smax = max(max(sizes), 1)
        buf = jnp.stack([jnp.pad(f, (0, smax - f.shape[0])) for f in flats])
        self._state_unflats = unflats
        return buf

    def unpack(self, buf=None):
        """[S, Lmax] buffer -> per-layer param list (checkpoint export)."""
        buf = self.params["stages"] if buf is None else buf
        buf = jax.device_get(buf)
        out = [None] * len(self.conf.layers)
        for s, g in enumerate(self.groups):
            stage_tree = self._unflats[s](jnp.asarray(buf[s]))
            for j, i in enumerate(g):
                out[i] = stage_tree[j]
        return out

    def unpack_state(self, buf=None):
        """[S, Smax] state slab -> per-layer state list (the
        MultiLayerNetwork.state shape — checkpoint/export interop)."""
        buf = self.state["stages"] if buf is None else buf
        buf = jax.device_get(buf)
        out = [None] * len(self.conf.layers)
        for s, g in enumerate(self.groups):
            stage_tree = self._state_unflats[s](jnp.asarray(buf[s]))
            for j, i in enumerate(g):
                out[i] = stage_tree[j]
        return out

    def init(self, rng=None, from_params=None, from_state=None):
        """``from_params`` / ``from_state``: MultiLayerNetwork-style
        per-layer lists (e.g. a trained net to pipeline) — the loss-pin
        path."""
        trees = (from_params if from_params is not None
                 else self._init_trees(rng if rng is not None
                                       else jax.random.PRNGKey(self.seed)))
        st_trees = (from_state if from_state is not None
                    else [layer.init_state(it) for layer, it
                          in zip(self.conf.layers, self.layer_inputs)])
        buf = self._pack(trees)
        sbuf = self._pack_state(st_trees)
        sh = NamedSharding(self.mesh, P("stage"))
        self.params = {"stages": jax.device_put(buf, sh)}
        self.param_shardings = {"stages": sh}
        self.state = {"stages": jax.device_put(sbuf, sh)}
        self.state_shardings = {"stages": sh}
        opt = self.updater.init(self.params)
        repl = NamedSharding(self.mesh, P())
        self._opt_sh = jax.tree_util.tree_map(
            lambda x: sh if getattr(x, "shape", None) == buf.shape else repl,
            opt)
        self.opt_state = jax.tree_util.tree_map(jax.device_put, opt,
                                                self._opt_sh)
        return self

    # -- stage programs --------------------------------------------------
    def _chain_keys(self, rng_mb):
        """Replicate MultiLayerNetwork.apply_fn's key-split chain over ALL
        layers, OUTSIDE the stage switch (the chain depends only on the
        per-microbatch key and the static layer list, never on the stage).
        Returns stacked [L, 2] uint32 key arrays (dropout key, layer key,
        weight-noise key per layer) so every switch branch consumes the
        same uniform operands — keeping threefry out of the branches,
        whose residual structures must match under partial-eval."""
        drop_k, layer_k, noise_k = [], [], []
        rng = rng_mb
        zero = jnp.zeros((2,), jnp.uint32)
        for layer in self.conf.layers:
            if layer.dropout:
                rng, sub_d = jax.random.split(rng)
            else:
                sub_d = zero
            rng, sub = jax.random.split(rng)
            if getattr(layer, "weight_noise", None) is not None:
                sub, nk = jax.random.split(sub)
            else:
                nk = zero
            drop_k.append(sub_d)
            layer_k.append(sub)
            noise_k.append(nk)
        return (jnp.stack(drop_k), jnp.stack(layer_k), jnp.stack(noise_k))

    def _keysets(self, rng):
        """[M, L, 2] uint32 key stacks for all microbatches — THE shared
        derivation both schedules use (their cross-schedule equality pin
        depends on it staying single-sourced). Zeros when rng is off."""
        if self._rng_active:
            return [jnp.stack(ks) for ks in zip(*(
                self._chain_keys(jax.random.fold_in(rng, m))
                for m in range(self.n_micro)))]
        return [jnp.zeros((self.n_micro, len(self.conf.layers), 2),
                          jnp.uint32) for _ in range(3)]

    @staticmethod
    def _pick_keys(ks, m):
        return lax.dynamic_index_in_dim(ks, m, axis=0, keepdims=False)

    def _stage_fn_full(self, s):
        """Stateful gpipe stage program: (slab [Lmax], state slab [Smax],
        flat act [mb, Amax], per-layer key stacks) -> (flat out, new
        state slab). Keys come pre-split from ``_chain_keys`` so
        dropout/noise draws are bit-identical to a sequential run of the
        same microbatch with the same per-microbatch key."""
        from deeplearning4j_tpu.nn.layers.base import dropout_mask
        g = self.groups[s]
        in_type = self.layer_inputs[g[0]]
        mb = self._mb
        in_shape = _type_shape(in_type, mb)
        in_size = int(np.prod(in_shape[1:]))
        unflat = self._unflats[s]
        sunflat = self._state_unflats[s]
        smax = self._smax
        use_rng = self._rng_active
        use_mask = self._mask_active

        def fn(slab, svec, aflat, mask, drop_k, layer_k, noise_k):
            pl_ = unflat(slab)
            sl_ = sunflat(svec)
            x = aflat[:, :in_size].reshape(in_shape)
            cur_type = in_type
            new_states = list(sl_)
            for li, i in enumerate(g):
                layer = self.conf.layers[i]
                fam = layer.input_family
                if fam is not None and not isinstance(cur_type, fam):
                    x = _inputs.adapt(x, cur_type, fam)
                    cur_type = _inputs.adapted_type(cur_type, fam)
                if use_rng and layer.dropout:
                    x = dropout_mask(drop_k[i], x, layer.dropout)
                p = pl_[li]
                wn = getattr(layer, "weight_noise", None)
                if use_rng and wn is not None and p:
                    p = wn.perturb(noise_k[i], layer, p)
                kwargs = ({"mask": mask}
                          if use_mask and self._mask_aware[i] else {})
                x, new_states[li] = layer.apply(
                    p, sl_[li], x, train=True,
                    rng=layer_k[i] if use_rng else None, **kwargs)
                cur_type = layer.output_type(cur_type)
            flat = x.reshape(mb, -1)
            sflat, _, _ = _flatten_tree(new_states)
            sout = jnp.pad(sflat, (0, smax - sflat.shape[0]))
            # uniform tangent structure: lax.switch's partial-eval (under
            # value_and_grad) requires every branch to expose the SAME
            # known/unknown output structure. State is a side effect
            # (running stats) — stop_gradient makes its tangent a symbolic
            # zero in EVERY branch; the activation gets an explicit
            # param-tangent tie so even a paramless stage's output is
            # tangent-carrying like the others.
            out = jnp.pad(flat,
                          ((0, 0), (0, self._amax - flat.shape[1])))
            out = out + slab[0] * 0
            return out, lax.stop_gradient(sout)
        return fn

    def _boundary_sizes(self, mb):
        sizes = []
        for g in self.groups:
            sizes.append(int(np.prod(_type_shape(
                self.layer_inputs[g[0]], mb)[1:])))
        sizes.append(int(np.prod(_type_shape(self.output_type, mb)[1:])))
        return sizes

    def _reg_penalty(self, pstages):
        """L1/L2 penalties over the packed stage buffer (reference
        calcL1/calcL2 semantics) — shared by both schedules."""
        pen = 0.0
        for s_idx, g in enumerate(self.groups):
            tree = self._unflats[s_idx](pstages[s_idx])
            for j, i in enumerate(g):
                if tree[j]:
                    pen = pen + self.conf.layers[i] \
                        .regularization_penalty(tree[j])
        return pen

    def _mask_mb(self, mask, mb):
        """Per-microbatch mask stack [M, mb, ...] (a dummy when off —
        switch operands must exist either way)."""
        if mask is not None:
            return jnp.asarray(mask).reshape(
                (self.n_micro, mb) + jnp.asarray(mask).shape[1:])
        return jnp.zeros((self.n_micro, mb, 1), jnp.float32)

    # -- loss / step -----------------------------------------------------
    def _loss_fn(self, params, states, x, y, rng=None, mask=None):
        """Returns (loss, new state slab dict) — differentiate with
        ``has_aux=True``. ``rng=None`` disables dropout/weight noise
        (matching MultiLayerNetwork.loss_fn's rng=None contract); BN
        still runs in train mode with microbatch statistics. ``mask``
        [B, T] reaches mask-aware layers AND the output loss (the
        MultiLayerNetwork.loss_fn mask contract)."""
        b = x.shape[0]
        mb = b // self.n_micro
        # stage branches run INSIDE shard_map: the microbatch axis is
        # sharded over 'data', so their static shapes use the local size
        self._mb = mb // self.mesh.shape.get("data", 1)
        self._amax = max(self._boundary_sizes(mb))
        self._smax = int(states["stages"].shape[1])
        self._rng_active = self.use_rng and rng is not None
        self._mask_active = mask is not None
        branches = [self._stage_fn_full(s) for s in range(self.n_stages)]
        n_micro, n_stages = self.n_micro, self.n_stages
        x_flat = x.reshape(n_micro, mb, -1)
        x_mb = jnp.pad(x_flat, ((0, 0), (0, 0),
                                (0, self._amax - x_flat.shape[-1])))
        mask_mb = self._mask_mb(mask, mb)
        # per-microbatch key chains, precomputed for ALL microbatches —
        # stage-independent, so they live outside the switch
        keysets = self._keysets(rng)

        def run(stages, svec, x_mb, mask_mb, drop_ks, layer_ks, noise_ks):
            s = lax.axis_index("stage")
            slab = stages[0]  # local [1, Lmax] -> [Lmax]
            st0 = svec[0]
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

            def tick(carry, t):
                buf, st = carry
                active = (t >= s) & (t - s < n_micro)
                mb_idx = jnp.clip(t - s, 0, n_micro - 1)
                fresh = lax.dynamic_index_in_dim(
                    x_mb, jnp.clip(t, 0, n_micro - 1), axis=0,
                    keepdims=False)
                x_in = jnp.where(s == 0, fresh, buf)
                yv, st_new = lax.switch(s, branches, slab, st, x_in,
                                        self._pick_keys(mask_mb, mb_idx),
                                        self._pick_keys(drop_ks, mb_idx),
                                        self._pick_keys(layer_ks, mb_idx),
                                        self._pick_keys(noise_ks, mb_idx))
                # state advances only on active ticks -> microbatch-order
                # sequential updates, same sequence as a per-microbatch
                # sequential run
                st = jnp.where(active, st_new, st)
                yv = jnp.where(active, yv, buf)
                out = jnp.where((s == n_stages - 1) & active, yv,
                                jnp.zeros_like(yv))
                nxt = lax.ppermute(yv, "stage", perm)
                return (nxt, st), out

            ticks = jnp.arange(n_micro + n_stages - 1)
            (_, st_fin), outs = lax.scan(
                tick, (jnp.zeros_like(x_mb[0]), st0), ticks)
            outs = outs[n_stages - 1:]
            if data_ax is not None:
                # ghost batch norm: per-shard stats averaged over 'data'
                # (the reference's per-worker BN under ParallelWrapper)
                st_fin = lax.pmean(st_fin, data_ax)
            return lax.psum(outs, "stage"), st_fin[None]

        data_ax = "data" if "data" in self.mesh.axis_names else None
        piped, new_sbuf = shard_map(
            run, mesh=self.mesh,
            in_specs=(P("stage"), P("stage"), P(None, data_ax),
                      P(None, data_ax), P(), P(), P()),
            out_specs=(P(None, data_ax), P("stage")),
            check_vma=False,
        )(params["stages"], states["stages"], x_mb, mask_mb, *keysets)
        out_size = self._boundary_sizes(mb)[-1]
        preds = piped[:, :, :out_size].reshape(
            (b,) + _type_shape(self.output_type, mb)[1:])
        out_layer = self.conf.layers[-1]
        loss = out_layer.compute_loss(preds, y, mask)
        # state must not leak gradients into the backward pass (the
        # running-stat update is a side effect, reference semantics)
        new_states = {"stages": lax.stop_gradient(new_sbuf)}
        return loss + self._reg_penalty(params["stages"]), new_states

    def loss(self, x, y, mask=None):
        l, _ = self._loss_fn(self.params, self.state, jnp.asarray(x),
                             jnp.asarray(y), None,
                             None if mask is None else jnp.asarray(mask))
        return l

    # -- 1F1B (explicit-VJP) schedule ------------------------------------
    def _loss_and_grads_1f1b(self, params, states, x, y, rng=None,
                             mask=None):
        """Loss + grads + new state via the shared combined-tick 1F1B
        engine (pipeline.run_combined_ticks, state0 thread). Differences
        from the LM family: the LOSS lives in the last stage's branch
        (the output layer's params are stage params, there is no external
        head) and stage dispatch is the lax.switch over heterogeneous
        branches. Residual stash: 2S-1 stage inputs; the backward half
        recomputes the stage forward — exact for BN (state-independent
        train forward) and for dropout (keys are deterministic [M, L, 2]
        operands indexed by microbatch, so the recompute redraws the same
        masks). Requires a mean-reduction per-example loss (the standard
        output layers) so microbatch contributions recompose exactly."""
        from deeplearning4j_tpu.parallel.pipeline import run_combined_ticks
        b = x.shape[0]
        mb = b // self.n_micro
        self._mb = mb // self.mesh.shape.get("data", 1)
        self._amax = max(self._boundary_sizes(mb))
        self._smax = int(states["stages"].shape[1])
        self._rng_active = self.use_rng and rng is not None
        self._mask_active = mask is not None
        branches = [self._stage_fn_full(s) for s in range(self.n_stages)]
        n_micro, n_stages = self.n_micro, self.n_stages
        out_layer = self.conf.layers[-1]
        out_shape = _type_shape(self.output_type, self._mb)
        out_size = int(np.prod(out_shape[1:]))
        x_flat = x.reshape(n_micro, mb, -1)
        x_mb = jnp.pad(x_flat, ((0, 0), (0, 0),
                                (0, self._amax - x_flat.shape[-1])))
        y_mb = y.reshape((n_micro, mb) + y.shape[1:])
        mask_mb = self._mask_mb(mask, mb)
        scale = self._mb / b  # per-mb mean -> full-batch mean
        # masked losses are mask-count-weighted means (losses.
        # _apply_mask_and_mean), so exact recomposition weights each
        # microbatch by its LOCAL mask count over the GLOBAL count
        denom_g = (jnp.maximum(jnp.sum(mask), 1.0)
                   if self._mask_active else jnp.ones((), jnp.float32))
        keysets = self._keysets(rng)

        def mb_loss(yflat, lab, lmask, dg):
            preds = yflat[:, :out_size].reshape(out_shape)
            if self._mask_active:
                return (out_layer.compute_loss(preds, lab, lmask)
                        * jnp.sum(lmask) / dg)
            return out_layer.compute_loss(preds, lab, None) * scale

        data_ax = "data" if "data" in self.mesh.axis_names else None

        def run(stages, svec, x_mb, y_mb, mask_mb, denom_g, drop_ks,
                layer_ks, noise_ks):
            s = lax.axis_index("stage")
            slab = stages[0]
            st0 = svec[0]

            def stage_apply(sl, a, st, m):
                return lax.switch(s, branches, sl, st, a,
                                  self._pick_keys(mask_mb, m),
                                  self._pick_keys(drop_ks, m),
                                  self._pick_keys(layer_ks, m),
                                  self._pick_keys(noise_ks, m))

            def bwd_seed(y_b, lab):
                loss_mb, lvjp = jax.vjp(
                    lambda h: mb_loss(h, lab["y"], lab["m"], denom_g),
                    y_b)
                (dy_last,) = lvjp(jnp.ones_like(loss_mb))
                return loss_mb, None, dy_last

            loss_acc, gslab, _, _, st_fin = run_combined_ticks(
                stage_apply, bwd_seed, n_micro, n_stages, slab, x_mb,
                {"y": y_mb, "m": mask_mb}, zero_aux=None,
                collect_dx=False, state0=st0)
            axes = ("stage",) if data_ax is None else ("stage", data_ax)
            loss = lax.psum(loss_acc, axes)
            if data_ax is not None:
                gslab = lax.psum(gslab, data_ax)
                st_fin = lax.pmean(st_fin, data_ax)  # ghost BN, as gpipe
            return loss, gslab[None], st_fin[None]

        loss, gstages, new_sbuf = shard_map(
            run, mesh=self.mesh,
            in_specs=(P("stage"), P("stage"), P(None, data_ax),
                      P(None, data_ax), P(None, data_ax), P(),
                      P(), P(), P()),
            out_specs=(P(), P("stage"), P("stage")),
            check_vma=False,
        )(params["stages"], states["stages"], x_mb, y_mb, mask_mb,
          denom_g, *keysets)
        # L1/L2 penalties live outside the schedule (the gpipe path
        # carries them in-loss via the same _reg_penalty helper)
        pen, dpen = jax.value_and_grad(self._reg_penalty)(params["stages"])
        return (loss + pen, {"stages": gstages + dpen},
                {"stages": lax.stop_gradient(new_sbuf)})

    def _build_step(self):
        upd = self.updater

        def step(params, states, opt_state, x, y, it, rng, mask):
            if self.schedule == "1f1b":
                loss, grads, new_states = self._loss_and_grads_1f1b(
                    params, states, x, y, rng, mask)
            else:
                (loss, new_states), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True)(params, states, x, y,
                                                 rng, mask)
            updates, opt_state = upd.update(grads, opt_state, params, it)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return params, new_states, opt_state, loss

        data_ax = "data" if "data" in self.mesh.axis_names else None
        data_sh = NamedSharding(self.mesh, P(data_ax))
        return jax.jit(
            step,
            # mask's sharding stays unspecified: the argument is None for
            # unmasked nets and ensure_sharded already placed it otherwise
            in_shardings=(self.param_shardings, self.state_shardings,
                          self._opt_sh, data_sh, data_sh, None, None,
                          None),
            out_shardings=(self.param_shardings, self.state_shardings,
                           self._opt_sh, NamedSharding(self.mesh, P())),
            donate_argnums=(0, 1, 2))

    def step(self, x, y, mask=None):
        if self.params is None:
            self.init()
        if self._step_fn is None:
            self._step_fn = self._build_step()
        data_ax = "data" if "data" in self.mesh.axis_names else None
        dsh = NamedSharding(self.mesh, P(data_ax))
        x = _mesh.ensure_sharded(x, dsh)
        y = _mesh.ensure_sharded(y, dsh)
        if mask is not None:
            mask = _mesh.ensure_sharded(jnp.asarray(mask), dsh)
        if self.use_rng:
            self._rng, step_key = jax.random.split(self._rng)
        else:
            step_key = jnp.zeros((2,), jnp.uint32)
        self.params, self.state, self.opt_state, loss = self._step_fn(
            self.params, self.state, self.opt_state, x, y, self.iteration,
            step_key, mask)
        self.iteration += 1
        if self.listeners:
            score = float(loss)  # one host sync, shared by all listeners
            for li in self.listeners:
                li.iteration_done(self, self.iteration, score)
        return loss


# ---------------------------------------------------------------------------
# ComputationGraph pipelining
# ---------------------------------------------------------------------------

def balance_graph_stages(conf, n_stages, order=None, types=None):
    """Contiguous topological-order stage boundaries for a
    GraphConfiguration, balancing per-stage param counts (the
    balance_stages greedy applied to vertices)."""
    order = order if order is not None else conf.topological_order()
    types = types if types is not None else conf.vertex_types()
    types = dict(types)
    for name, it in zip(conf.inputs, conf.input_types):
        types[name] = it
    defs = {v.name: v for v in conf.vertices}
    assert n_stages <= len(order)
    key = jax.random.PRNGKey(0)
    counts = []
    for name in order:
        v = defs[name]
        in_types = [types[i] for i in v.inputs]
        p = jax.eval_shape(lambda k, _v=v.vertex, _t=in_types:
                           _v.init(k, _t), key)
        counts.append(sum(int(np.prod(l.shape))
                          for l in jax.tree_util.tree_leaves(p)))
    return [order[a:b] for a, b in _greedy_balance(counts, n_stages)]


class PipelinedGraph:
    """GPipe-pipeline any single-input / single-output ComputationGraph
    over a mesh 'stage' axis (reference role: ParallelWrapper.java:58
    wraps any Model — ComputationGraph included).

    The DAG is cut into contiguous topological-order vertex groups; each
    stage boundary carries EVERY tensor still live across it (outputs of
    earlier groups consumed by later ones), flattened and concatenated
    into the rotating [mb, Amax] GPipe buffer. Skip connections of any
    span therefore stage without restriction: a tensor crossing several
    boundaries simply rides the buffer through the intermediate stages.
    BN running stats thread through the per-stage state slab exactly as
    in PipelinedNetwork; the output vertex's forward runs in the last
    stage and the loss (+ L1/L2) is computed outside the pipelined
    region, so the loss is pinned to ComputationGraph.loss_fn on the
    same params. ``schedule="1f1b"`` runs the combined-tick engine with
    the state thread (exact: BN's train forward is state-independent
    and stages are rng-free here, so the backward-half recompute is
    bit-faithful). Constraints (asserted): no dropout / weight noise /
    aux losses inside the pipelined region, no masks.
    """

    def __init__(self, conf, mesh: Mesh, *, n_microbatches=4,
                 stage_vertices=None, updater=None, seed=None,
                 schedule="gpipe"):
        assert "stage" in mesh.axis_names, "mesh needs a 'stage' axis"
        assert schedule in ("gpipe", "1f1b"), schedule
        assert len(conf.inputs) == 1 and len(conf.outputs) == 1, \
            "PipelinedGraph stages single-input/single-output graphs"
        self.conf = conf
        self.mesh = mesh
        self.schedule = schedule
        self.n_micro = n_microbatches
        self.n_stages = mesh.shape["stage"]
        self.updater = updater or conf.updater
        self.seed = conf.seed if seed is None else seed
        self.order = conf.topological_order()
        assert self.order[-1] == conf.outputs[0], \
            "the output vertex must be the topological sink"
        self.defs = {v.name: v for v in conf.vertices}
        self.types = dict(conf.vertex_types())
        self.types[conf.inputs[0]] = conf.input_types[0]
        assert conf.gradient_normalization in (None, "none"), \
            "PipelinedGraph does not apply gradient normalization; " \
            "clip on the sequential ComputationGraph path"
        for v in conf.vertices:
            layer = getattr(v.vertex, "layer", None)
            assert getattr(layer, "dropout", 0.0) in (0.0, None), \
                f"vertex {v.name}: no dropout inside PipelinedGraph"
            assert getattr(layer, "weight_noise", None) is None, \
                f"vertex {v.name}: no weight noise inside PipelinedGraph"
            assert not hasattr(layer, "aux_loss_weight") \
                and not hasattr(v.vertex, "aux_loss_weight"), \
                f"vertex {v.name}: aux-loss layers are not stageable"
        out_v = self.defs[conf.outputs[0]]
        assert not hasattr(getattr(out_v.vertex, "layer", None),
                           "loss_from_features"), \
            "feature-loss heads (CenterLossOutputLayer) compute their " \
            "loss from pre-head activations ComputationGraph.loss_fn " \
            "threads specially; not stageable — use the sequential graph"
        self.groups = (stage_vertices if stage_vertices is not None
                       else balance_graph_stages(conf, self.n_stages,
                                                 self.order, self.types))
        assert len(self.groups) == self.n_stages
        assert [n for g in self.groups for n in g] == self.order, \
            "stage_vertices must be contiguous topo-order groups"
        self._boundaries = self._compute_boundaries()
        self.params = None
        self.state = None
        self.opt_state = None
        self._step_fn = None
        self.iteration = 0
        self.listeners = []

    def add_listener(self, listener):
        """TrainingListener fired after every step (reference:
        ParallelWrapper.setListeners). Firing syncs the loss to host —
        attach only when the telemetry is wanted. (Param-stat listeners
        see the packed stage slab; num_params() is the true count.)"""
        self.listeners.append(listener)
        return self

    def num_params(self):
        """True (unpadded) parameter count of the packed stage slab."""
        return self._n_params

    # -- structure -------------------------------------------------------
    def _compute_boundaries(self):
        """boundaries[k] = ordered tensor names live ENTERING stage k:
        the graph input for k=0; for k>0, outputs of groups <k (or the
        input) still consumed by groups >=k. An extra final entry holds
        the output vertex alone (what leaves the last stage)."""
        in_name = self.conf.inputs[0]
        consumed_at = {}  # name -> last stage index that consumes it
        for k, g in enumerate(self.groups):
            for vn in g:
                for src in self.defs[vn].inputs:
                    consumed_at[src] = max(consumed_at.get(src, -1), k)
        bounds = [[in_name]]
        for k in range(1, self.n_stages):
            produced = [in_name] + [n for g in self.groups[:k] for n in g]
            live = [n for n in produced
                    if consumed_at.get(n, -1) >= k]
            bounds.append(live)
        bounds.append([self.conf.outputs[0]])
        return bounds

    def _flat_size(self, name, mb):
        return int(np.prod(_type_shape(self.types[name], mb)[1:]))

    def _boundary_sizes(self, mb):
        return [sum(self._flat_size(n, mb) for n in b)
                for b in self._boundaries]

    # -- packing ---------------------------------------------------------
    def _pack(self, vertex_params):
        flats, unflats, sizes = [], [], []
        for g in self.groups:
            f, u, n = _flatten_tree({vn: vertex_params[vn] for vn in g})
            flats.append(f)
            unflats.append(u)
            sizes.append(n)
        lmax = max(max(sizes), 1)
        buf = jnp.stack([jnp.pad(f, (0, lmax - f.shape[0]))
                         for f in flats])
        self._unflats = unflats
        self._n_params = sum(sizes)
        return buf

    def _pack_state(self, vertex_states):
        flats, unflats, sizes = [], [], []
        for g in self.groups:
            f, u, n = _flatten_tree({vn: vertex_states[vn] for vn in g})
            flats.append(f)
            unflats.append(u)
            sizes.append(n)
        smax = max(max(sizes), 1)
        buf = jnp.stack([jnp.pad(f, (0, smax - f.shape[0]))
                         for f in flats])
        self._state_unflats = unflats
        return buf

    def unpack(self, buf=None):
        """Stage buffer -> {vertex: params} (ComputationGraph.params
        shape — checkpoint/export interop)."""
        buf = self.params["stages"] if buf is None else buf
        buf = jax.device_get(buf)
        out = {}
        for s in range(self.n_stages):
            out.update(self._unflats[s](jnp.asarray(buf[s])))
        return out

    def unpack_state(self, buf=None):
        buf = self.state["stages"] if buf is None else buf
        buf = jax.device_get(buf)
        out = {}
        for s in range(self.n_stages):
            out.update(self._state_unflats[s](jnp.asarray(buf[s])))
        return out

    def init(self, rng=None, from_params=None, from_state=None):
        if from_params is not None:
            ptrees = from_params
        else:
            rng = rng if rng is not None else jax.random.PRNGKey(self.seed)
            ptrees = {}
            for name in self.order:
                rng, sub = jax.random.split(rng)
                v = self.defs[name]
                in_types = [self.types[i] for i in v.inputs]
                ptrees[name] = v.vertex.init(sub, in_types)
        st_trees = (from_state if from_state is not None else {
            name: self.defs[name].vertex.init_state(
                [self.types[i] for i in self.defs[name].inputs])
            for name in self.order})
        buf = self._pack(ptrees)
        sbuf = self._pack_state(st_trees)
        sh = NamedSharding(self.mesh, P("stage"))
        self.params = {"stages": jax.device_put(buf, sh)}
        self.param_shardings = {"stages": sh}
        self.state = {"stages": jax.device_put(sbuf, sh)}
        self.state_shardings = {"stages": sh}
        opt = self.updater.init(self.params)
        repl = NamedSharding(self.mesh, P())
        self._opt_sh = jax.tree_util.tree_map(
            lambda x: sh if getattr(x, "shape", None) == buf.shape
            else repl, opt)
        self.opt_state = jax.tree_util.tree_map(jax.device_put, opt,
                                                self._opt_sh)
        return self

    # -- stage programs --------------------------------------------------
    def _stage_fn(self, k):
        """(slab [Lmax], state slab [Smax], boundary flat [mb, Amax]) ->
        (next boundary flat, new state slab)."""
        group = self.groups[k]
        in_names = self._boundaries[k]
        out_names = self._boundaries[k + 1]
        mb = self._mb
        in_shapes = [_type_shape(self.types[n], mb) for n in in_names]
        in_sizes = [int(np.prod(sh[1:])) for sh in in_shapes]
        unflat = self._unflats[k]
        sunflat = self._state_unflats[k]
        smax = self._smax

        def fn(slab, svec, bflat):
            pl_ = unflat(slab)
            sl_ = sunflat(svec)
            vals, off = {}, 0
            for name, sh, sz in zip(in_names, in_shapes, in_sizes):
                vals[name] = bflat[:, off:off + sz].reshape(sh)
                off += sz
            new_states = dict(sl_)
            for name in group:
                v = self.defs[name]
                xs = [vals[i] for i in v.inputs]
                y, st = v.vertex.apply(pl_[name], sl_[name], xs,
                                       train=True, rng=None)
                vals[name] = y
                new_states[name] = st
            flat = jnp.concatenate(
                [vals[n].reshape(mb, -1) for n in out_names], axis=1)
            sflat, _, _ = _flatten_tree(new_states)
            sout = jnp.pad(sflat, (0, smax - sflat.shape[0]))
            out = jnp.pad(flat, ((0, 0), (0, self._amax - flat.shape[1])))
            # uniform tangent structure across switch branches (see
            # PipelinedNetwork._stage_fn_full)
            return out + slab[0] * 0, lax.stop_gradient(sout)
        return fn

    def _reg_penalty(self, pstages):
        pen = 0.0
        for s, g in enumerate(self.groups):
            tree = self._unflats[s](pstages[s])
            for name in g:
                if tree[name]:
                    pen = pen + self.defs[name].vertex \
                        .regularization_penalty(tree[name])
        return pen

    # -- loss / step -----------------------------------------------------
    def _loss_fn(self, params, states, x, y):
        """(loss, new state slab dict) — has_aux. Same tick loop as
        PipelinedNetwork._loss_fn over the graph stage programs."""
        b = x.shape[0]
        mb = b // self.n_micro
        self._mb = mb // self.mesh.shape.get("data", 1)
        self._amax = max(self._boundary_sizes(mb))
        self._smax = int(states["stages"].shape[1])
        branches = [self._stage_fn(s) for s in range(self.n_stages)]
        n_micro, n_stages = self.n_micro, self.n_stages
        x_flat = x.reshape(n_micro, mb, -1)
        x_mb = jnp.pad(x_flat, ((0, 0), (0, 0),
                                (0, self._amax - x_flat.shape[-1])))

        def run(stages, svec, x_mb):
            s = lax.axis_index("stage")
            slab = stages[0]
            st0 = svec[0]
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

            def tick(carry, t):
                buf, st = carry
                active = (t >= s) & (t - s < n_micro)
                fresh = lax.dynamic_index_in_dim(
                    x_mb, jnp.clip(t, 0, n_micro - 1), axis=0,
                    keepdims=False)
                x_in = jnp.where(s == 0, fresh, buf)
                yv, st_new = lax.switch(s, branches, slab, st, x_in)
                st = jnp.where(active, st_new, st)
                yv = jnp.where(active, yv, buf)
                out = jnp.where((s == n_stages - 1) & active, yv,
                                jnp.zeros_like(yv))
                nxt = lax.ppermute(yv, "stage", perm)
                return (nxt, st), out

            ticks = jnp.arange(n_micro + n_stages - 1)
            (_, st_fin), outs = lax.scan(
                tick, (jnp.zeros_like(x_mb[0]), st0), ticks)
            outs = outs[n_stages - 1:]
            if data_ax is not None:
                st_fin = lax.pmean(st_fin, data_ax)  # ghost batch norm
            return lax.psum(outs, "stage"), st_fin[None]

        data_ax = "data" if "data" in self.mesh.axis_names else None
        piped, new_sbuf = shard_map(
            run, mesh=self.mesh,
            in_specs=(P("stage"), P("stage"), P(None, data_ax)),
            out_specs=(P(None, data_ax), P("stage")),
            check_vma=False,
        )(params["stages"], states["stages"], x_mb)
        out_name = self.conf.outputs[0]
        out_size = self._flat_size(out_name, mb)
        preds = piped[:, :, :out_size].reshape(
            (b,) + _type_shape(self.types[out_name], mb)[1:])
        out_layer = self.defs[out_name].vertex.layer
        loss = out_layer.compute_loss(preds, y, None)
        new_states = {"stages": lax.stop_gradient(new_sbuf)}
        return loss + self._reg_penalty(params["stages"]), new_states

    def loss(self, x, y):
        l, _ = self._loss_fn(self.params, self.state, jnp.asarray(x),
                             jnp.asarray(y))
        return l

    # -- 1F1B (explicit-VJP) schedule ------------------------------------
    def _loss_and_grads_1f1b(self, params, states, x, y):
        """Loss + grads + new state via the shared combined-tick engine
        (pipeline.run_combined_ticks, state0 thread) over the graph
        stage programs — the PipelinedNetwork 1f1b path minus keys and
        masks (stages here are rng-free by construction)."""
        from deeplearning4j_tpu.parallel.pipeline import run_combined_ticks
        b = x.shape[0]
        mb = b // self.n_micro
        self._mb = mb // self.mesh.shape.get("data", 1)
        self._amax = max(self._boundary_sizes(mb))
        self._smax = int(states["stages"].shape[1])
        branches = [self._stage_fn(s) for s in range(self.n_stages)]
        n_micro, n_stages = self.n_micro, self.n_stages
        out_name = self.conf.outputs[0]
        out_layer = self.defs[out_name].vertex.layer
        out_shape = _type_shape(self.types[out_name], self._mb)
        out_size = int(np.prod(out_shape[1:]))
        x_flat = x.reshape(n_micro, mb, -1)
        x_mb = jnp.pad(x_flat, ((0, 0), (0, 0),
                                (0, self._amax - x_flat.shape[-1])))
        y_mb = y.reshape((n_micro, mb) + y.shape[1:])
        scale = self._mb / b  # per-mb mean -> full-batch mean

        def mb_loss(yflat, lab):
            preds = yflat[:, :out_size].reshape(out_shape)
            return out_layer.compute_loss(preds, lab, None) * scale

        data_ax = "data" if "data" in self.mesh.axis_names else None

        def run(stages, svec, x_mb, y_mb):
            s = lax.axis_index("stage")
            slab = stages[0]
            st0 = svec[0]

            def stage_apply(sl, a, st, m):
                del m  # rng-free stages: microbatch index unused
                return lax.switch(s, branches, sl, st, a)

            def bwd_seed(y_b, lab):
                loss_mb, lvjp = jax.vjp(lambda h: mb_loss(h, lab), y_b)
                (dy_last,) = lvjp(jnp.ones_like(loss_mb))
                return loss_mb, None, dy_last

            loss_acc, gslab, _, _, st_fin = run_combined_ticks(
                stage_apply, bwd_seed, n_micro, n_stages, slab, x_mb,
                y_mb, zero_aux=None, collect_dx=False, state0=st0)
            axes = ("stage",) if data_ax is None else ("stage", data_ax)
            loss = lax.psum(loss_acc, axes)
            if data_ax is not None:
                gslab = lax.psum(gslab, data_ax)
                st_fin = lax.pmean(st_fin, data_ax)  # ghost BN, as gpipe
            return loss, gslab[None], st_fin[None]

        loss, gstages, new_sbuf = shard_map(
            run, mesh=self.mesh,
            in_specs=(P("stage"), P("stage"), P(None, data_ax),
                      P(None, data_ax)),
            out_specs=(P(), P("stage"), P("stage")),
            check_vma=False,
        )(params["stages"], states["stages"], x_mb, y_mb)
        pen, dpen = jax.value_and_grad(self._reg_penalty)(params["stages"])
        return (loss + pen, {"stages": gstages + dpen},
                {"stages": lax.stop_gradient(new_sbuf)})

    def _build_step(self):
        upd = self.updater

        def step(params, states, opt_state, x, y, it):
            if self.schedule == "1f1b":
                loss, grads, new_states = self._loss_and_grads_1f1b(
                    params, states, x, y)
            else:
                (loss, new_states), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True)(params, states, x, y)
            updates, opt_state = upd.update(grads, opt_state, params, it)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return params, new_states, opt_state, loss

        data_ax = "data" if "data" in self.mesh.axis_names else None
        data_sh = NamedSharding(self.mesh, P(data_ax))
        return jax.jit(
            step,
            in_shardings=(self.param_shardings, self.state_shardings,
                          self._opt_sh, data_sh, data_sh, None),
            out_shardings=(self.param_shardings, self.state_shardings,
                           self._opt_sh, NamedSharding(self.mesh, P())),
            donate_argnums=(0, 1, 2))

    def step(self, x, y):
        if self.params is None:
            self.init()
        if self._step_fn is None:
            self._step_fn = self._build_step()
        data_ax = "data" if "data" in self.mesh.axis_names else None
        dsh = NamedSharding(self.mesh, P(data_ax))
        x = _mesh.ensure_sharded(x, dsh)
        y = _mesh.ensure_sharded(y, dsh)
        self.params, self.state, self.opt_state, loss = self._step_fn(
            self.params, self.state, self.opt_state, x, y, self.iteration)
        self.iteration += 1
        if self.listeners:
            score = float(loss)  # one host sync, shared by all listeners
            for li in self.listeners:
                li.iteration_done(self, self.iteration, score)
        return loss
