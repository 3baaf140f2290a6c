"""Data-parallel (+ optional tensor-parallel) training over a device mesh.

Reference analog — ALL of these collapse into this module (SURVEY.md §2.5/§5):
- ParallelWrapper parameter averaging (ParallelWrapper.java:250-338):
  N replicas + periodic ``Nd4j.averageAndPropagate``;
- EncodedGradientsAccumulator threshold-compressed async gradient sharing
  (EncodedGradientsAccumulator.java, EncodingHandler.java);
- Spark ParameterAveragingTrainingMaster / SharedTrainingMaster + Aeron
  VoidParameterServer (SharedTrainingMaster.java:469).

TPU-native: params replicated over the ``data`` axis, batch sharded over it,
and the jitted train step's gradient reduction lowers to an exact XLA
all-reduce over ICI/DCN — synchronous and exact, strictly stronger than the
reference's lossy asynchronous threshold scheme, with none of the user-space
transport. Optional tensor parallelism: per-layer param PartitionSpecs shard
weight matrices over the ``model`` axis; XLA inserts the activation
collectives.

The reference's separate "averaging frequency" machinery is unnecessary —
per-step all-reduce is the synchronous limit of averaging every step — but
``average_every`` is supported for loose (local-SGD style) training.

Weight-update sharding (Xu et al. 2020, arxiv 2004.13336) is the DEFAULT:
optimizer state lives in the ZeRO-1 layout (param sharding + 'data' on the
first divisible dim, ``mesh.zero1_sharding``), the step constrains the
grad→update boundary so the gradient reduction feeds the sharded update
directly (reduce-scatter on TPU; CPU's partitioner emits the decomposed
all-reduce + dynamic-slice), and params all-gather back out.
``shard_params="fsdp"`` is one tier deeper: params are STORED in the same
1/N layout between steps and gathered inside the step. For Adam (3 copies
of P), ZeRO-1 cuts steady-state per-replica bytes from 3P to P + 2P/N and
FSDP to ~3P/N — capacity that buys bigger per-chip batches (the
measured-MFU item on the ROADMAP; the realized numbers are the
``param_bytes``/``opt_state_bytes`` gauges on ``/health``). Honest scope
of plain fsdp: the gather is one constraint over the whole tree at step
entry — XLA schedules the all-gathers, but nothing forces a layer-by-layer
gather-use-discard, so the WITHIN-step peak still holds the full params
alongside activations; what it frees is everything those trees pinned
BETWEEN steps.

``shard_params="fsdp_stream"`` closes that remaining ZeRO-3 half (Rajbhandari
et al. 2019, arxiv 1910.02054 §5.3): the network's homogeneous trunk — a run
of identical layers, the same stacked-slab pytree discipline
parallel/pipeline.py scans — is stacked ``[L, ...]`` INSIDE the step and
scanned block by block, each block's params all-gathered from their
``P('data')`` shards inside the scan body, used, and discarded; the body is
``jax.checkpoint``'d so the backward sweep RE-gathers each block instead of
stashing L gathered copies, and the gather constraint's transpose
reduce-scatters each block's grads straight back into the shard — neither
the full param tree nor the full grad tree ever materializes. Within-step
peak = one block's weights + activations (``step_peak_bytes`` gauges /
``compiled.memory_analysis()``, gated streamed < fsdp in
scripts/check_zero.py), and the HLO shows ONE block-shaped all-gather
inside the scan's while body instead of L hoisted to step entry.
"""

from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops import spmd as _spmd
from deeplearning4j_tpu.parallel import mesh as _mesh
from deeplearning4j_tpu.telemetry import devices as _devices


def _layer_param_spec(layer, pname, arr):
    """Tensor-parallel PartitionSpec for one parameter array.

    Dense-family kernels [n_in, n_out] shard the output dim over 'model'
    (Megatron column parallelism); biases follow the output dim; conv kernels
    HWIO shard the O dim. Everything else is replicated. Shapes not divisible
    by the model-axis size stay replicated (XLA requires even shards).
    """
    spec = [None] * arr.ndim
    if pname.startswith("expert_"):
        # MoE stacked expert weights [E, ...]: shard the EXPERT axis over
        # 'model' — GSPMD partitions the per-expert einsums and inserts the
        # dispatch/combine all-to-alls (expert parallelism)
        spec[0] = "model"
    elif pname in ("W", "Wx", "Wh") and arr.ndim >= 2:
        spec[-1] = "model"
    elif pname in ("b", "beta", "gamma") and arr.ndim == 1:
        spec[0] = "model"
    return P(*spec)


def _layer_param_items(net, params):
    """(layer, param_dict) pairs for either container: MultiLayerNetwork
    keeps a list aligned with conf.layers; ComputationGraph keeps a dict
    keyed by vertex name (layer may be None for layerless vertices)."""
    if isinstance(params, dict):
        def layer_of(name):
            vdef = net._defs.get(name)
            v = getattr(vdef, "vertex", None)
            return getattr(v, "layer", None)
        return [(layer_of(name), name, params[name]) for name in params]
    return [(layer, i, p) for i, (layer, p)
            in enumerate(zip(net.conf.layers, params))]


def _chunked_device_get(tree):
    """Host copy of a device pytree ONE LEAF at a time: ``tree_map``
    visits leaves sequentially and ``jax.device_get`` on a single array
    blocks until it is assembled, so at most one layer's gathered copy
    is in flight. The contract this helper pins (don't "simplify" it to
    ``jax.device_get(tree)``): the whole-tree form launches every
    leaf's shard fetch concurrently, which for an FSDP-sharded model
    briefly stages the entire gathered tree in transfer buffers —
    exactly the fit-end spike the sharded layout exists to avoid. Works
    for any registered pytree, container types preserved."""
    return jax.tree_util.tree_map(lambda a: jax.device_get(a), tree)


def streamable_trunk(net, params, state):
    """``(i0, i1)`` bounds of the longest homogeneous trunk the streamed
    ZeRO-3 step can scan — a run of >= 2 identical, stateless,
    param-carrying layers (same frozen-dataclass config, same input type,
    same param treedef/shapes/dtypes) that excludes the output layer —
    or None. Identical layers applied to a stable input type are exactly
    a ``lax.scan`` over their stacked param slab; statelessness keeps the
    scan carry to (activation, rng) so the bit-exactness contract with
    the unrolled ``apply_fn`` loop is just the rng-split order."""
    layers = getattr(getattr(net, "conf", None), "layers", None)
    if layers is None or isinstance(params, dict) or params is None:
        return None
    n = len(layers)
    frozen = set(getattr(net, "frozen_layers", ()))

    def leaf_sig(p):
        leaves, treedef = jax.tree_util.tree_flatten(p)
        return (treedef, tuple((tuple(np.shape(l)),
                                str(getattr(l, "dtype", type(l).__name__)))
                               for l in leaves))

    def eligible(i):
        return (i < n - 1 and i not in frozen and bool(params[i])
                and not jax.tree_util.tree_leaves(state[i]))

    def same(i, j):
        return (type(layers[i]) is type(layers[j])
                and layers[i] == layers[j]          # frozen dataclasses
                and net.layer_inputs[i] == net.layer_inputs[j]
                and leaf_sig(params[i]) == leaf_sig(params[j]))

    best, i = None, 0
    while i < n:
        if not eligible(i):
            i += 1
            continue
        j = i + 1
        while j < n and eligible(j) and same(i, j):
            j += 1
        if j - i >= 2 and (best is None or (j - i) > (best[1] - best[0])):
            best = (i, j)
        i = j
    return best


def make_param_shardings(mesh: Mesh, net, params, tensor_parallel=False):
    """Sharding pytree matching the params container (list for
    MultiLayerNetwork, dict for ComputationGraph)."""
    tp_size = mesh.shape["model"]
    items = _layer_param_items(net, params)
    out = {} if isinstance(params, dict) else [None] * len(items)
    repl = NamedSharding(mesh, P())
    for layer, key, p in items:
        if tensor_parallel and tp_size > 1 and layer is not None:
            def spec_for(path, v, _layer=layer):
                # last path element names the parameter. Nested sub-dicts
                # (MoE blocks' ln/mha params) only match the expert rule:
                # the Megatron W/bias rules assume a flat dense-family
                # layer and would wrongly shard e.g. LayerNorm gamma.
                last = path[-1]
                pname = getattr(last, "key", str(last))
                if len(path) > 1 and not pname.startswith("expert_"):
                    return NamedSharding(mesh, P())
                spec = _layer_param_spec(_layer, pname, v)
                # only shard when divisible
                ok = all(s is None or v.shape[i] % tp_size == 0
                         for i, s in enumerate(spec))
                return NamedSharding(mesh, spec if ok else P())
            out[key] = jax.tree_util.tree_map_with_path(spec_for, p)
        else:
            out[key] = jax.tree_util.tree_map(lambda _: repl, p)
    return out


class ParallelTrainer:
    """Sharded trainer around a MultiLayerNetwork's or ComputationGraph's
    functional core (both expose the same make_train_step contract).

    Usage:
        trainer = ParallelTrainer(net, mesh)
        trainer.init()
        for batch in data:
            loss = trainer.step(x, y)
    """

    def __init__(self, net, mesh: Mesh | None = None, *, tensor_parallel=False,
                 donate=True, shard_optimizer_state=True, shard_params=None):
        self.net = net
        self.mesh = mesh if mesh is not None else _mesh.make_mesh()
        self.tensor_parallel = tensor_parallel
        self.donate = donate
        if shard_params not in (None, "fsdp", "fsdp_stream"):
            raise ValueError(
                f"shard_params={shard_params!r}: None (replicated between "
                "steps), 'fsdp' (ZeRO-3 storage: params stored P('data') "
                "between steps, whole-tree gather at step entry) or "
                "'fsdp_stream' (ZeRO-3 streamed: the homogeneous trunk is "
                "scanned block-by-block, each block gathered inside the "
                "scan body and discarded — step-peak HBM is one block, "
                "not the model)")
        # ZeRO-1 / cross-replica weight-update sharding (Xu et al. 2020,
        # arxiv 2004.13336 — the paper behind GSPMD's optimizer sharding)
        # is the DEFAULT: optimizer-state leaves split over the 'data' axis
        # (derived FROM the param shardings via mesh.zero1_sharding, so a
        # tensor-parallel leaf's moments keep their 'model' axes and are
        # never resharded against their param), Adam moments cost HBM/N
        # per replica, and the step pins the grad→update boundary with
        # with_sharding_constraint so XLA reduce-scatters gradients into
        # the sharded update and all-gathers params out (on CPU the
        # partitioner emits the decomposed all-reduce+dynamic-slice pair;
        # TPU/GPU pipelines fuse it into a reduce-scatter — inspected in
        # tests/test_zero.py, not assumed). ``shard_params="fsdp"`` grows
        # this one tier deeper (ZeRO-3): params themselves are STORED in
        # the zero1 layout between steps and gathered per step.
        self.shard_optimizer_state = bool(shard_optimizer_state) \
            or shard_params in ("fsdp", "fsdp_stream")
        self.shard_params = shard_params
        self._step_fn = None
        self._score_fn = None
        self.params = None
        self.state = None
        self.opt_state = None
        self.iteration = 0
        self.epoch = 0
        self.score_value = None
        self.listeners = []
        self._rng = jax.random.PRNGKey(net.conf.seed)

    def add_listener(self, listener):
        """Attach a TrainingListener fired once per fit() iteration plus
        on_epoch_end per epoch (reference: ParallelWrapper.setListeners —
        score/stats listeners observe the parallel fit exactly as they
        observe a plain net.fit). NOTE: firing needs the loss on host, so
        each iteration pays one device sync — attach listeners only when
        you want the telemetry (the bare step() loop stays sync-free)."""
        self.listeners.append(listener)
        return self

    def num_params(self):
        return self.net.num_params()

    @property
    def conf(self):
        return self.net.conf

    def output(self, x, mask=None):
        """Inference through the trained params (EvaluativeListener and
        friends call this on the model they observe): sync the latest
        mesh params into the wrapped net, then run its output."""
        self.sync_to_net()
        return self.net.output(x, mask=mask)

    def _derive_shardings(self, params, opt):
        """All four sharding trees from a (host or device) params/opt
        TEMPLATE — structure and shapes only, no arrays are placed:

        * ``param_shardings``       compute layout (replicated / TP)
        * ``param_store_shardings`` between-step storage — the compute
          layout, or its zero1 'data' extension under FSDP (ZeRO-3,
          all-gathered inside the step)
        * ``_opt_leaf_shards``      per-param-leaf layout of the opt
          state (and the grad→update constraint)
        * ``_opt_shardings``        the full updater-state tree
        """
        self.param_shardings = make_param_shardings(
            self.mesh, self.net, params, self.tensor_parallel)
        # ONE zero1 tree serves both uses: FSDP's between-step param
        # storage and the opt-state layout are the same extension rule
        # by design (the constructor forces shard_optimizer_state on
        # under fsdp), so build it once and alias
        zero1_tree = (jax.tree_util.tree_map(
            lambda s, p: _mesh.zero1_sharding(self.mesh, s, p),
            self.param_shardings, params)
            if self.shard_optimizer_state else None)
        self.param_store_shardings = (zero1_tree
                                      if self.shard_params
                                      in ("fsdp", "fsdp_stream")
                                      else self.param_shardings)
        self._opt_leaf_shards = (zero1_tree if self.shard_optimizer_state
                                 else self.param_shardings)
        self._opt_shardings = _mesh.opt_shardings_like(
            opt, params, self._opt_leaf_shards,
            NamedSharding(self.mesh, P()))
        # a stateless updater (Sgd, NoOp: state=()) has nothing to shard
        # — routing it through the constrained step would pay the
        # reduce-scatter/all-gather machinery every step for zero saved
        # bytes. FSDP still needs the constrained step (the PARAMS are
        # sharded); plain ZeRO-1 falls back to the unconstrained path.
        self._zero_step_active = (
            self.shard_params in ("fsdp", "fsdp_stream")
            or (self.shard_optimizer_state
                and any(hasattr(l, "shape")
                        for l in jax.tree_util.tree_leaves(opt))))

    def _place(self, params, state, opt):
        """Derive the layouts and put all three trees on the mesh — ONE
        definition shared by init() and adopt_net_state(), so a
        fresh-init and a checkpoint-resumed trainer can never place (or
        account) their trees differently."""
        if self.shard_params == "fsdp_stream":
            # the streamed step needs the stacked-slab trunk; detect it on
            # the HOST template so an unstreamable net fails loudly at
            # placement, not as an opaque trace error inside the scan
            from deeplearning4j_tpu.nn.layers import base as _lbase
            _lbase.refuse_loss_mask_layers(self.net.conf.layers,
                                           "shard_params='fsdp_stream'")
            self._trunk = streamable_trunk(self.net, params, state)
            if (self._trunk is None or self.net.conf.ties
                    or hasattr(self.net.conf.layers[-1],
                               "loss_from_features")):
                raise ValueError(
                    "shard_params='fsdp_stream' needs a homogeneous trunk "
                    "to scan: >= 2 consecutive identical stateless layers "
                    "(same config, same param shapes) below a standard "
                    "loss head. This net has none — use "
                    "shard_params='fsdp' (whole-tree gather) instead")
        self._derive_shardings(params, opt)
        self.params = jax.tree_util.tree_map(jax.device_put, params,
                                             self.param_store_shardings)
        self.state = jax.device_put(state, NamedSharding(self.mesh, P()))
        self.opt_state = jax.tree_util.tree_map(jax.device_put, opt,
                                                self._opt_shardings)
        _devices.note_train_tree_bytes(params=self.params,
                                       opt_state=self.opt_state,
                                       site="parallel_trainer")

    def init(self, rng=None):
        params, state = self.net.init(rng)
        self._place(params, state, self.net.conf.updater.init(params))
        return self

    def adopt_net_state(self):
        """Place the wrapped net's (host) params/state/opt_state/RNG chain
        and counters onto the mesh in THIS trainer's layouts — the resume
        path from a single-process checkpoint (utils.serialization
        load_model/load_bundle): a replicated zip resumes into a ZeRO-1 or
        FSDP trainer, the layout re-derived here rather than trusted from
        the file. The inverse of ``sync_to_net``. The net's own trees are
        the sharding template — no throwaway re-init or placement of a
        fresh model (a resume is the cold-start path; it pays exactly one
        device_put per adopted tree)."""
        net = self.net
        params, state, opt = net.params, net.state, net.opt_state
        if params is None:
            raise ValueError(
                "adopt_net_state: the wrapped net has no params — load a "
                "checkpoint into it (utils.serialization load_model/"
                "load_bundle) or net.init() first")
        if opt is None:
            opt = net.conf.updater.init(params)
        self._place(params, state, opt)
        rng = getattr(net, "_rng", None)
        if rng is not None:
            self._rng = jnp.asarray(rng)
        self.iteration = int(getattr(net, "iteration", 0))
        self.epoch = int(getattr(net, "epoch", 0))
        return self

    @property
    def layout(self):
        """The storage-layout name ('replicated' | 'zero1' | 'fsdp' |
        'fsdp_stream') — the label on the HBM/step-peak gauges and the
        bench.py zero leg keys."""
        if self.shard_params:
            return self.shard_params
        return "zero1" if self.shard_optimizer_state else "replicated"

    def _streamed_loss(self):
        """Mirror of ``MultiLayerNetwork.loss_fn`` with the homogeneous
        trunk scanned instead of unrolled: the per-layer forward is the
        net's own ``_apply_layer`` (one definition — the rng-split /
        dropout / adapt order cannot drift), but the trunk's stacked slab
        rides a ``lax.scan`` whose checkpointed body gathers ONE block
        from its ``P('data')`` shards, applies it, and discards it — the
        ZeRO-3 streamed gather. Regularization penalties accumulate as a
        per-block scan output and are re-added in original layer order,
        so the addition order (and hence the bits) match the unrolled
        loss exactly."""
        net, mesh = self.net, self.mesh
        i0, i1 = self._trunk
        layers = net.conf.layers
        n = len(layers)
        trunk_layer = layers[i0]
        gather_sh = self.param_shardings
        block_gather = gather_sh[i0]
        slab_store = jax.tree_util.tree_map(
            lambda s: _mesh.slab_sharding(mesh, s),
            self.param_store_shardings[i0])
        wsc = jax.lax.with_sharding_constraint

        from deeplearning4j_tpu.nn import losses as _losses
        from deeplearning4j_tpu.nn.conf import inputs as _inputs
        from deeplearning4j_tpu.nn.layers import base as _lbase
        from deeplearning4j_tpu.parallel.pipeline import stack_blocks

        def loss_fn(params, state, x, y, rng, mask):
            out_layer = layers[-1]
            if not hasattr(out_layer, "compute_loss"):
                raise ValueError(
                    "Last layer must be an output/loss layer, got "
                    f"{type(out_layer).__name__}")
            logits_loss = _losses.from_logits(out_layer)
            new_state = list(state)

            def edge(i, h, rng, cur_type):
                # non-trunk layers gather individually just-in-time (XLA
                # may still hoist these few; the trunk is the bulk)
                full = (jax.tree_util.tree_map(wsc, params[i],
                                               gather_sh[i])
                        if params[i] else params[i])
                h, new_state[i], rng, cur_type = net._apply_layer(
                    i, full, state[i], h, cur_type, train=True, rng=rng,
                    mask=mask, logits=logits_loss is not None and i == n - 1)
                return h, rng, cur_type

            h, cur_type = x, net.conf.input_type
            for i in range(i0):
                h, rng, cur_type = edge(i, h, rng, cur_type)
            # the trunk's one-time input adaptation: apply_fn adapts at
            # the FIRST block and the type is stable after it, so inside
            # the scan body _apply_layer must see the adapted type
            fam = trunk_layer.input_family
            if fam is not None and not isinstance(cur_type, fam):
                h = _inputs.adapt(h, cur_type, fam)
                cur_type = _inputs.adapted_type(cur_type, fam)
            slab = stack_blocks(params[i0:i1])
            slab = jax.tree_util.tree_map(wsc, slab, slab_store)
            st0, ct = state[i0], cur_type

            def body(carry, bp):
                h, rng = carry
                # the per-block all-gather: constraining the slab SLICE
                # to the compute layout inside the loop body is what XLA
                # cannot hoist — one block lives gathered at a time, and
                # the constraint's transpose reduce-scatters this block's
                # grads straight back into the shard
                bp_full = jax.tree_util.tree_map(wsc, bp, block_gather)
                h, _, rng, _ = net._apply_layer(
                    i0, bp_full, st0, h, ct, train=True, rng=rng,
                    mask=mask)
                pen = trunk_layer.regularization_penalty(bp_full)
                # scan stacks the per-block penalties into an array; a
                # python-float 0.0 (no l1/l2 configured) needs a dtype,
                # a traced penalty keeps its own (x64-safe)
                if isinstance(pen, float):
                    pen = jnp.asarray(pen, jnp.float32)
                return (h, rng), pen

            # checkpoint: the backward sweep RE-gathers each block from
            # its shards instead of stashing i1-i0 gathered copies — the
            # residual per block is the sharded slice + the activation
            body = jax.checkpoint(body)
            (h, rng), pens = jax.lax.scan(body, (h, rng), slab)
            cur_type = trunk_layer.output_type(ct)
            for i in range(i1, n):
                h, rng, cur_type = edge(i, h, rng, cur_type)
            # as MultiLayerNetwork.loss_fn: a softmax head's from its logits
            loss = (logits_loss or out_layer.compute_loss)(h, y, mask)
            preds = h if logits_loss is None \
                else out_layer.activation_fn()(h)
            for i in range(n):
                if i0 <= i < i1:
                    loss = loss + pens[i - i0]
                elif params[i]:
                    full = jax.tree_util.tree_map(wsc, params[i],
                                                  gather_sh[i])
                    loss = loss + layers[i].regularization_penalty(full)
            loss, new_state = _lbase.pop_aux_losses(loss, new_state)
            return loss, (new_state, preds)

        return loss_fn

    def _streamed_update_step(self):
        """``_sharded_update_step`` for the fsdp_stream tier: same
        make_train_step signature and the same grad→update constraint
        chain, but the loss is the streamed-trunk mirror, differentiated
        w.r.t. the STORED (sharded) params — grads arrive through the
        gather constraints' transposes already reduce-scattered, so the
        full grad tree never materializes either."""
        from deeplearning4j_tpu.nn import gradnorm as _gradnorm

        net = self.net
        store_sh = self.param_store_shardings
        grad_sh = self._opt_leaf_shards
        wsc = jax.lax.with_sharding_constraint
        loss_fn = self._streamed_loss()

        def step(params, state, opt_state, x, y, it, rng, mask=None):
            (loss, (new_state, _)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, x, y, rng, mask)
            grads = _gradnorm.normalize_grads(
                net.conf.gradient_normalization, grads,
                net.conf.gradient_normalization_threshold)
            grads = jax.tree_util.tree_map(wsc, grads, grad_sh)
            new_params, new_opt = net.apply_update(params, opt_state,
                                                   grads, it)
            new_params = jax.tree_util.tree_map(wsc, new_params, store_sh)
            return new_params, new_state, new_opt, loss

        return step

    def _sharded_update_step(self):
        """The net's single train step with the ZeRO grad→update boundary
        made explicit (make_train_step signature, shared by the K=1 jit
        and the fused K-step scan): FSDP-stored params gather to the
        compute layout inside the step, gradients pin to the opt-shard
        layout — the constraint XLA lowers to a reduce-scatter feeding
        the sharded update — and the new params' storage constraint
        all-gathers them back out. The fsdp_stream tier swaps in the
        streamed-trunk loss (``_streamed_update_step``) under the same
        contract."""
        if self.shard_params == "fsdp_stream":
            return self._streamed_update_step()
        net = self.net
        gather_sh = self.param_shardings
        store_sh = self.param_store_shardings
        grad_sh = self._opt_leaf_shards
        fsdp = self.shard_params == "fsdp"
        wsc = jax.lax.with_sharding_constraint

        def step(params, state, opt_state, x, y, it, rng, mask=None):
            if fsdp:
                # ZeRO-3: params live sharded between steps; constraining
                # to the compute layout IS the per-step all-gather
                full = jax.tree_util.tree_map(wsc, params, gather_sh)
            else:
                full = params
            loss, new_state, grads = net.compute_gradients(
                full, state, x, y, rng=rng, mask=mask)
            grads = jax.tree_util.tree_map(wsc, grads, grad_sh)
            new_params, new_opt = net.apply_update(params, opt_state, grads,
                                                   it)
            if fsdp:
                new_params = jax.tree_util.tree_map(wsc, new_params,
                                                    store_sh)
            return new_params, new_state, new_opt, loss

        return step

    def _resolve_donate(self, donate):
        """PR 9's warm-manifest donation-off rule, respected here too: a
        net with an attached warm manifest runs every engine without
        buffer donation (deserialized executables lose jax's aliasing
        guard; the trainer keeps the uniform rule so a bundle-resumed job
        behaves identically through every fit path)."""
        if donate and getattr(self.net, "_warm_manifest", None) is not None:
            import warnings
            if not getattr(self, "_warned_manifest_donate", False):
                # say so once (the nn/fused convention): peak HBM for
                # params/opt_state grows with donation off, and nothing
                # else in the logs would explain why
                self._warned_manifest_donate = True
                warnings.warn(
                    "warm manifest attached to the wrapped net: buffer "
                    "donation is disabled for the ParallelTrainer engines "
                    "(serialized executables lose jax's aliasing guard) — "
                    "detach the manifest (attach_manifest(net, None)) if "
                    "memory-bound", stacklevel=3)
            return False
        return donate

    def _build_step(self, donate):
        base_step = (self._sharded_update_step()
                     if self._zero_step_active
                     else self.net.make_train_step(jit=False))
        donate = self._resolve_donate(donate)
        data_sh = _mesh.data_sharded(self.mesh)
        repl = NamedSharding(self.mesh, P())
        opt_sh = self._opt_shardings

        # in: params, state, opt, x, y, step, rng, mask — the mask shards
        # over 'data' WITH its batch (replicating it per dispatch would
        # broadcast [B,...] host bytes to every replica for nothing)
        in_sh = (self.param_store_shardings,
                 jax.tree_util.tree_map(lambda _: repl, self.state),
                 opt_sh, data_sh, data_sh, None, repl, data_sh)
        out_sh = (self.param_store_shardings,
                  jax.tree_util.tree_map(lambda _: repl, self.state),
                  opt_sh, repl, repl)

        def step(params, state, opt_state, x, y, it, rng, mask=None):
            # rng chain advances INSIDE the step: one dispatch per
            # iteration instead of a separate host-side split
            rng_next, sub = jax.random.split(rng)
            # Pallas kernels reached while tracing run per batch shard
            with _spmd.kernel_mesh(self.mesh):
                out = base_step(params, state, opt_state, x, y, it, sub,
                                mask)
            return out + (rng_next,)

        return jax.jit(step,
                       in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0, 1, 2, 6) if donate else ())

    def step(self, x, y, mask=None):
        if self.params is None:
            self.init()
        if self._step_fn is None:
            self._step_fn = self._build_step(self.donate)
        x = _mesh.ensure_data_sharded(self.mesh, x)
        y = _mesh.ensure_data_sharded(self.mesh, y)
        if mask is not None:
            mask = _mesh.ensure_data_sharded(self.mesh, mask)
        (self.params, self.state, self.opt_state, loss,
         self._rng) = self._step_fn(
            self.params, self.state, self.opt_state, x, y, self.iteration,
            self._rng, mask)
        self.score_value = loss  # device scalar; float() on demand
        self.iteration += 1
        return loss

    def profile_round(self, rounds_from_now, logdir, force=None):
        """Arm a windowed ``jax.profiler`` capture around the n-th future
        fit round (one epoch of the driver loop; ``rounds_from_now=1`` is
        the next). No-op off-TPU — see telemetry/profiling.py and
        PROFILE.md. The armed schedule is handed to the StepDriver the
        next :meth:`fit` builds."""
        from deeplearning4j_tpu.telemetry import profiling as _profiling
        sched = getattr(self, "_profile_schedule", None)
        if sched is None:
            sched = self._profile_schedule = _profiling.ProfileSchedule()
        sched.arm(rounds_from_now, logdir, force=force)
        return sched

    def fit(self, x, y=None, *, epochs=1, batch_size=None, mask=None,
            steps_per_dispatch=1):
        """Train on arrays, an (x, y) pair, OR any DataSetIterator (the
        reference's signature entry point,
        ParallelWrapper.fit(DataSetIterator) at ParallelWrapper.java:58 —
        async/prefetching iterators included; batch unpacking is shared
        with MultiLayerNetwork.fit via datasets.iterator.iter_batches).

        Batches whose leading dim is not divisible by the mesh 'data'
        axis are SKIPPED (the data sharding cannot place them) and
        counted in ``self.examples_dropped`` — the array path has always
        dropped the ragged tail the same way.

        ``steps_per_dispatch=K`` runs K steps per dispatch through the
        fused ``lax.scan`` engine (nn/fused.py) over super-batches
        sharded ``[K, B/data, ...]``: ragged batches pad to the bucketed
        shape (validity in the loss mask, exact) instead of being
        dropped, and the super-batch assembly + sharded ``device_put``
        overlap the running dispatch on the prefetch thread."""
        import warnings

        from deeplearning4j_tpu.datasets.iterator import iter_batches

        is_iterator = (y is None and hasattr(x, "__iter__")
                       and not isinstance(x, (tuple, list))
                       and not hasattr(x, "shape"))
        if is_iterator and (batch_size is not None or mask is not None):
            raise ValueError("batch_size/mask have no effect with an "
                             "iterator input: the iterator owns its own "
                             "batching and per-batch masks")
        if int(steps_per_dispatch) > 1:
            return self._fit_fused(x, y, epochs=epochs,
                                   batch_size=batch_size, mask=mask,
                                   k=int(steps_per_dispatch))
        # the loop is the shared StepDriver (continuous/driver.py) in its
        # lite profile — the sharded engine wraps self.step, listener
        # scores resolve one step late through the driver's ScorePipeline
        # (graftlint R1; the MultiLayerNetwork.fit pipelining convention)
        from deeplearning4j_tpu.continuous.driver import (
            StepDriver, _ShardedPlainEngine)

        data_size = self.mesh.shape["data"]
        self.examples_dropped = 0
        drv = StepDriver(self, lambda: iter_batches(x, y, batch_size, mask),
                         engine=_ShardedPlainEngine(self),
                         instrumented=False)
        drv.profile = getattr(self, "_profile_schedule", None)
        self._run_epochs(drv, epochs, data_size)
        if self.examples_dropped:
            warnings.warn(f"ParallelTrainer.fit dropped "
                          f"{self.examples_dropped} examples in ragged "
                          f"batches not divisible by data={data_size}")
        return drv.last_score

    def _run_epochs(self, drv, epochs, data_size):
        """N epochs of driver rounds with the trainer's historical
        epoch-edge contract: an empty first epoch is a hard error, an
        exhausted generator on a later epoch is too (silently "training"
        zero steps would lie to the caller), and epoch-end listeners fire
        only for epochs that trained."""
        for epoch in range(epochs):
            rr = drv.run_round(None)
            if rr.steps == 0 and epoch == 0:
                raise ValueError(
                    "no trainable batches: every batch's leading dim must "
                    f"be divisible by the data-axis size {data_size}")
            if rr.steps == 0 and epoch > 0:
                raise ValueError(
                    f"input exhausted before epoch {epoch + 1}: pass a "
                    "resettable DataSetIterator (or arrays) for epochs>1")
            for li in self.listeners:
                li.on_epoch_end(self)
            self.epoch += 1

    def _build_steps_fused(self, k, donate):
        """Sharded fused K-step engine: the raw scan from nn/fused.py
        jitted with the trainer's param/opt shardings, super-batches
        sharded [K, B/data, ...] and the RNG chain carried through the
        dispatch (the _build_step conventions, amortized K-fold). Under
        ZeRO the scan body is the trainer's constrained step, so the
        sharded opt state is CARRIED through all K steps — reduce-scatter
        grads / sharded update / all-gather params happen inside the scan
        body, K times per dispatch, with no host round-trip between."""
        from deeplearning4j_tpu.nn import fused as _fused

        base = _fused.make_train_steps(
            self.net, k, jit=False,
            base_step=(self._sharded_update_step()
                       if self._zero_step_active else None))
        donate = self._resolve_donate(donate)
        repl = NamedSharding(self.mesh, P())
        sb_sh = _mesh.superbatch_sharded(self.mesh)
        state_sh = jax.tree_util.tree_map(lambda _: repl, self.state)
        opt_sh = self._opt_shardings

        # in: params, state, opt, xs, ys, step0, rng, masks, step_valid
        in_sh = (self.param_store_shardings, state_sh, opt_sh, sb_sh, sb_sh,
                 None, repl, sb_sh, repl)
        out_sh = (self.param_store_shardings, state_sh, opt_sh, repl, repl)

        def steps(params, state, opt_state, xs, ys, step0, rng, masks, sv):
            rng_next, sub = jax.random.split(rng)
            with _spmd.kernel_mesh(self.mesh):
                out = base(params, state, opt_state, xs, ys, step0, sub,
                           masks, sv)
            return out + (rng_next,)

        return jax.jit(steps, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0, 1, 2, 6) if donate else ())

    def _fit_fused(self, x, y, *, epochs, batch_size, mask, k):
        """fit() at steps_per_dispatch=K: one sharded dispatch per K
        minibatches; scores resolve one dispatch late as stacked arrays
        (the ScorePipeline discipline, amortized). The loop is the shared
        StepDriver's lite profile over the sharded fused engine —
        super-batch assembly + sharded ``device_put`` overlap the running
        dispatch on the prefetch thread exactly as before."""
        from deeplearning4j_tpu.datasets.iterator import iter_batches
        from deeplearning4j_tpu.continuous.driver import (
            StepDriver, _ShardedFusedEngine)

        if self.params is None:
            self.init()
        data_size = self.mesh.shape["data"]
        # validate BEFORE the prefetch thread: its sharded device_put hits
        # the non-divisible dim first and would surface as a raw sharding
        # error instead of this message
        feats = x[0] if (y is None and isinstance(x, (tuple, list))) else x
        nominal = batch_size if batch_size is not None else (
            feats.shape[0] if hasattr(feats, "shape") else None)
        if nominal is not None and nominal % data_size:
            raise ValueError(
                f"bucketed batch size {nominal} not divisible by the "
                f"data-axis size {data_size}")
        self.examples_dropped = 0  # bucketing pads; nothing is dropped
        eng = _ShardedFusedEngine(self, k)
        eng.batch_size = batch_size
        drv = StepDriver(self, lambda: iter_batches(x, y, batch_size, mask),
                         engine=eng, instrumented=False)
        drv.profile = getattr(self, "_profile_schedule", None)
        try:
            self._run_epochs(drv, epochs, data_size)
        finally:
            drv.close_source()
        return drv.last_score

    def _fan_listener_scores(self, scores, meta):
        """K per-step listener callbacks from one resolved fused
        dispatch (padded K-tail entries already dropped via meta['k'])."""
        k = meta["k"]
        it0 = meta["iteration"] - k
        for j, s in enumerate(scores[:k]):
            for li in self.listeners:
                li.iteration_done(self, it0 + j + 1, s)

    def score(self, x, y, mask=None):
        """Validation loss on the mesh — the DataSetLossCalculator contract,
        so EarlyStoppingTrainer drives a ParallelTrainer directly (reference:
        TestParallelEarlyStopping)."""
        if self.params is None:
            self.init()
        if self._score_fn is None:
            def base(p, s, x, y, m):
                with _spmd.kernel_mesh(self.mesh):
                    return self.net.loss_fn(p, s, x, y, train=False,
                                            mask=m)[0]
            self._score_fn = jax.jit(base)
        # early stopping scores the SAME validation arrays every epoch:
        # cache the sharded device copies, keyed by weakrefs to the host
        # arrays — live-referent identity subsumes id()/shape checks and
        # cannot alias a recycled address (raw id()s can, after GC)
        deref = lambda r: r() if isinstance(r, weakref.ref) else r
        refs = getattr(self, "_score_cache_refs", None)
        hit = (refs is not None
               and deref(refs[0]) is x and deref(refs[1]) is y)
        if not hit:
            def mkref(a):
                try:
                    return weakref.ref(a)
                except TypeError:
                    return a  # non-weakref-able (e.g. list): strong ref
            self._score_cache_refs = (mkref(x), mkref(y))
            self._score_cache = (
                jax.device_put(jnp.asarray(x), _mesh.data_sharded(self.mesh)),
                jax.device_put(jnp.asarray(y), _mesh.data_sharded(self.mesh)))
        xd, yd = self._score_cache
        return float(self._score_fn(self.params, self.state, xd, yd, mask))

    def step_memory_analysis(self, x, y, mask=None):
        """Compile the current step ahead-of-time for ``(x, y[, mask])``
        and export its ``compiled.memory_analysis()`` ledger into the
        ``step_peak_bytes`` gauges (labeled by this trainer's layout) —
        the within-step peak the steady-state ``tree_shard_bytes`` gauges
        cannot see, and the number the fsdp_stream tier exists to shrink.
        Routed through the blessed ``compile_cache.aot_compile`` site (a
        second, analysis-only compile — call it from benches/operators,
        not per step). Returns the stats dict, or None when the backend
        has no memory analysis."""
        from deeplearning4j_tpu.utils import compile_cache as _cc

        if self.params is None:
            self.init()
        if self._step_fn is None:
            self._step_fn = self._build_step(self.donate)
        x = _mesh.ensure_data_sharded(self.mesh, x)
        y = _mesh.ensure_data_sharded(self.mesh, y)
        if mask is not None:
            mask = _mesh.ensure_data_sharded(self.mesh, mask)
        ex, _src = _cc.aot_compile(
            self._step_fn, self.params, self.state, self.opt_state, x, y,
            self.iteration, self._rng, mask,
            kind=f"trainer_step:{self.layout}")
        return _devices.note_step_peak_bytes(
            "parallel_trainer", ex, layout=self.layout)

    def sync_to_net(self):
        """Copy trained params back into the wrapped MultiLayerNetwork.
        ``device_get`` gathers whatever the storage layout is — FSDP
        shards included — so the result is always a full host copy the
        single-process checkpoint formats (save_model/save_bundle) can
        write; ``adopt_net_state`` is the inverse. The gather goes
        through ``_chunked_device_get`` — leaf-at-a-time, each transfer
        complete before the next starts — so ending a large FSDP fit
        stages at most one assembled array on the host, a contract the
        named helper pins against a whole-tree ``jax.device_get``
        (concurrent shard fetch of the entire model) creeping in."""
        self.net.params = _chunked_device_get(self.params)
        self.net.state = _chunked_device_get(self.state)
        self.net.opt_state = _chunked_device_get(self.opt_state)
        self.net._rng = jax.device_get(self._rng)
        self.net.iteration = self.iteration
        self.net.epoch = self.epoch
        return self.net
