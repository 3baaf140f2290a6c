"""Pipeline parallelism: GPipe microbatch schedule over a mesh ``stage`` axis.

Reference analog: none — DL4J has no pipeline parallelism (its scaleout tier
is data-parallel only: ParallelWrapper.java, the Spark TrainingMasters).
Net-new for the TPU scale goals, alongside tensor (parallel/mesh.py) and
sequence (parallel/sequence.py) parallelism.

TPU-first design (the scaling-book recipe, functional-jax style):
* The repeated trunk of the model (identical transformer blocks) is STACKED
  into one pytree with a leading block axis, sharded ``P('stage')`` — each
  device owns a contiguous slab of blocks and its weights never move.
* Inside ``shard_map``, the classic GPipe schedule runs as a ``lax.scan``
  over ticks: at tick t, stage s processes microbatch t-s, then hands its
  activation to stage s+1 with a single ``lax.ppermute`` hop over ICI.
  Stage 0 injects fresh microbatches; stage S-1 collects finished ones.
* The BACKWARD schedule is not hand-written: ``jax.grad`` differentiates
  through scan + ppermute, and the transpose of a ppermute is the reverse
  ppermute — AD derives the reverse pipeline automatically.
* Embedding + head run OUTSIDE the pipelined region (replicated / data
  sharded): they are a tiny fraction of the FLOPs and keeping them out
  keeps every pipeline stage homogeneous.

Composes with data parallelism on the same mesh: batch microbatches shard
over ``data`` while blocks shard over ``stage`` (tested on a 2x4 CPU mesh).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from deeplearning4j_tpu.parallel import mesh as _mesh
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import updaters as U
from deeplearning4j_tpu.nn.conf import inputs as I


def stack_blocks(blocks):
    """Stack per-block param trees into ONE slab pytree with a leading
    block axis — the stacked-slab discipline every scanned or pipelined
    trunk rides: PipelineParallelLM / ComposedParallelLM shard the
    leading axis ``P('stage')`` (each device owns a contiguous run of
    blocks), while the ZeRO-3 streamed step
    (data_parallel._streamed_loss) keeps it whole and scans it, sharding
    the WITHIN-block dims ``P('data')`` instead (mesh.slab_sharding).
    Same pytree, two orthogonal axes over it — which is exactly why the
    two tiers compose on one mesh."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)


def _stage_fn_of(block, remat=False):
    """Shared stage body: scan a device's stacked block slab over an
    activation. ``block`` is a layer object (``apply(params, {}, x)``) or a
    plain ``bp, h -> y`` function (the composed facade passes its
    tensor-parallel block forward here)."""
    if callable(block) and not hasattr(block, "apply"):
        def one_block(bp, h):
            return block(bp, h)
    else:
        def one_block(bp, h):
            y, _ = block.apply(bp, {}, h)
            return y

    if remat:
        one_block = jax.checkpoint(one_block)

    def stage_fn(local_blocks, x):
        def body(h, bp):
            return one_block(bp, h), None
        h, _ = lax.scan(body, x, local_blocks)
        return h
    return stage_fn


def lm_head_loss(scale):
    """Per-microbatch LM loss closure shared by every 1F1B caller:
    sum of token NLLs times ``scale`` (pick scale = 1/(B*T) so summing
    over microbatches and data shards reproduces the full-batch mean)."""
    def head_loss(hp, h, lab):
        logits = h @ hp["W"] + hp["b"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, lab[..., None].astype(jnp.int32),
                                   axis=-1)
        return jnp.sum(nll) * scale
    return head_loss


def gpipe_schedule(block, n_micro, n_stages, remat=False):
    """Per-device GPipe schedule body (call inside shard_map over 'stage').

    ``block``: the (static) layer object whose ``apply(params, {}, x)`` runs
    one block. Returns ``run(local_blocks, x_mb)`` where ``local_blocks`` is
    the device's stacked slab [L/S, ...] and ``x_mb`` is [M, mb, T, D]
    microbatched activations (same on every stage; only stage 0 reads them).
    Output: [M, mb, T, D] finished activations (identical on every stage).

    ``remat``: rematerialize each block's forward during the backward
    schedule (jax.checkpoint) — GPipe's activation stash shrinks from every
    intra-block intermediate to one activation per block per in-flight
    microbatch, the standard HBM-for-FLOPs trade for deep pipelines.
    """
    stage_fn = _stage_fn_of(block, remat)

    def run(local_blocks, x_mb):
        s = lax.axis_index("stage")
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(buf, t):
            # stage s processes microbatch t-s at tick t
            active = (t >= s) & (t - s < n_micro)
            fresh = lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
            x_in = jnp.where(s == 0, fresh, buf)
            y = stage_fn(local_blocks, x_in)
            y = jnp.where(active, y, buf)
            out = jnp.where((s == n_stages - 1) & active, y,
                            jnp.zeros_like(y))
            nxt = lax.ppermute(y, "stage", perm)
            return nxt, out

        ticks = jnp.arange(n_micro + n_stages - 1)
        _, outs = lax.scan(tick, jnp.zeros_like(x_mb[0]), ticks)
        # microbatch m finishes on stage S-1 at tick m + S - 1
        outs = outs[n_stages - 1:]
        # every other stage contributed zeros: one psum broadcasts the
        # finished activations to all stages (its transpose routes the
        # cotangent straight back to stage S-1)
        return lax.psum(outs, "stage")

    return run


def one_f_one_b_schedule(block, n_micro, n_stages, head_loss,
                         extra_axes=()):
    """1F1B schedule (Megatron-style non-interleaved): each combined tick
    runs ONE microbatch forward and ONE microbatch backward per stage, with
    explicit VJPs instead of whole-schedule AD.

    Why: GPipe's backward is derived by differentiating the forward scan,
    so every in-flight microbatch's activations stay stashed until the
    backward sweep — the stash grows with M. Here backward for microbatch
    m starts as soon as its forward clears the last stage; only the stage
    INPUT per in-flight microbatch is saved (2S-1 slots, independent of M)
    and the stage forward recomputes inside its VJP — the standard
    1F1B-with-recompute memory profile that lets M grow (and the relative
    bubble (S-1)/M shrink) without the activation stash growing.

    Tick arithmetic: fwd(m, s) at tick m + s; bwd(m, s) at tick
    m + 2(S-1) - s. The last stage runs F and B of the same microbatch in
    one tick; cotangents hop backward over the reverse ppermute ring.

    ``head_loss(head_p, h_mb, lab_mb)`` must return the SCALED scalar loss
    contribution of one microbatch's final activations (so that summing
    over microbatches — and over ``data_axis`` shards — gives the
    full-batch loss); its VJP seeds the backward wave on stage S-1 and
    yields the head grads.

    Returns ``run(local_blocks, head_p, x_mb, lab_mb) ->
    (loss, dblocks_local, dhead, dx_mb)`` for use inside shard_map over
    'stage'. ``extra_axes``: mesh axes that shard the activation dims
    (e.g. ('data',) or ('data', 'seq')) — block/head grads and the loss
    psum over them inside; tensor-parallel axes must NOT be listed (their
    reductions are the transposes of the block's own collectives).
    """

    stage_fn = _stage_fn_of(block)

    def run(local_blocks, head_p, x_mb, lab_mb):
        def bwd_seed(y_b, lab):
            loss_mb, head_vjp = jax.vjp(
                lambda hp, h: head_loss(hp, h, lab), head_p, y_b)
            dhead_mb, dy_head = head_vjp(jnp.ones_like(loss_mb))
            return loss_mb, dhead_mb, dy_head

        zero_head = jax.tree_util.tree_map(jnp.zeros_like, head_p)
        loss_acc, gblocks, ghead, dx_acc = run_combined_ticks(
            stage_fn, bwd_seed, n_micro, n_stages, local_blocks, x_mb,
            lab_mb, zero_aux=zero_head, collect_dx=True)
        # loss/head grads live on stage S-1, dx on stage 0: psums broadcast;
        # extra_axes shard the activation dims, so replicated-param grads
        # and the loss also sum over them
        stage_extra = ("stage",) + tuple(extra_axes)
        loss = lax.psum(loss_acc, stage_extra)
        ghead = jax.tree_util.tree_map(
            lambda g: lax.psum(g, stage_extra), ghead)
        if extra_axes:
            gblocks = jax.tree_util.tree_map(
                lambda g: lax.psum(g, tuple(extra_axes)), gblocks)
        dx_mb = lax.psum(dx_acc, "stage")
        return loss, gblocks, ghead, dx_mb

    return run


def run_combined_ticks(stage_fn, bwd_seed, n_micro, n_stages, stage_params,
                       x_mb, lab_mb, *, zero_aux=None, collect_dx=False,
                       state0=None):
    """The 1F1B combined-tick engine shared by every schedule variant
    (the LM family above; the heterogeneous PipelinedNetwork). Call
    inside shard_map over 'stage'.

    ``stage_fn(stage_params, act) -> act`` is one stage's forward (its
    VJP yields the stage grads). ``bwd_seed(y_last, lab) ->
    (loss_mb, aux_grads, dy)`` computes one microbatch's scaled loss on
    the LAST stage's output and seeds the backward wave; ``aux_grads``
    (e.g. head grads) accumulate only on the last stage — pass
    ``zero_aux`` with their structure, or None when the loss has no
    parameters outside the stages. Returns the LOCAL
    (loss_acc, gparams, aux_acc, dx_acc) — callers apply the psums their
    sharding needs.

    ``state0`` (optional) threads MUTABLE stage state (BN running stats)
    through the schedule: stage_fn's signature becomes
    ``stage_fn(params, act, state, mb_idx) -> (act, new_state)`` and a
    fifth element — the final state — is returned. The forward half
    advances state in microbatch order; the backward half RECOMPUTES the
    forward against the current state, which is exact only when the
    stage forward is state-independent in train mode (true of BN, which
    normalizes with batch statistics — the running stats are a side
    effect). ``mb_idx`` lets stage programs select per-microbatch
    dropout keys deterministically, so the recompute redraws identical
    masks (same contract as jax.checkpoint over dropout).
    """
    s = lax.axis_index("stage")
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    n_slots = 2 * n_stages - 1  # max residual lifetime in ticks
    stateful = state0 is not None

    zero_act = jnp.zeros_like(x_mb[0])
    zero_params = jax.tree_util.tree_map(jnp.zeros_like, stage_params)

    def tick(carry, t):
        (a_buf, g_buf, resid, gparams, aux_acc, dx_acc, loss_acc,
         st) = carry
        # ---- forward half ----
        m_f = t - s
        f_active = (m_f >= 0) & (m_f < n_micro)
        m_fc = jnp.clip(m_f, 0, n_micro - 1)
        fresh = lax.dynamic_index_in_dim(x_mb, m_fc, axis=0,
                                         keepdims=False)
        x_in = jnp.where(s == 0, fresh, a_buf)
        if stateful:
            y_f, st_new = stage_fn(stage_params, x_in, st, m_fc)
            st = jax.tree_util.tree_map(
                lambda new, old: jnp.where(f_active, new, old), st_new, st)
        else:
            y_f = stage_fn(stage_params, x_in)
        slot_f = jnp.mod(m_fc, n_slots)
        saved = jnp.where(f_active, x_in,
                          lax.dynamic_index_in_dim(resid, slot_f, axis=0,
                                                   keepdims=False))
        resid = lax.dynamic_update_index_in_dim(resid, saved, slot_f,
                                                axis=0)
        a_next = lax.ppermute(jnp.where(f_active, y_f, zero_act),
                              "stage", fwd_perm)
        # ---- backward half ----
        m_b = t - 2 * (n_stages - 1) + s
        b_active = (m_b >= 0) & (m_b < n_micro)
        m_bc = jnp.clip(m_b, 0, n_micro - 1)
        slot_b = jnp.mod(m_bc, n_slots)
        x_saved = lax.dynamic_index_in_dim(resid, slot_b, axis=0,
                                           keepdims=False)
        # lab_mb may be a pytree (labels + per-microbatch masks)
        lab = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, m_bc, axis=0,
                                               keepdims=False), lab_mb)
        if stateful:
            st_c = jax.tree_util.tree_map(lax.stop_gradient, st)
            y_b, vjp = jax.vjp(
                lambda p, x: stage_fn(p, x, st_c, m_bc)[0],
                stage_params, x_saved)
        else:
            y_b, vjp = jax.vjp(stage_fn, stage_params, x_saved)
        loss_mb, aux_mb, dy_last = bwd_seed(y_b, lab)
        dy = jnp.where(s == n_stages - 1, dy_last, g_buf)
        dp_mb, dx_mb = vjp(dy)
        bact = b_active.astype(jnp.float32)
        gparams = jax.tree_util.tree_map(
            lambda g, d: g + bact * d, gparams, dp_mb)
        last = (b_active & (s == n_stages - 1)).astype(jnp.float32)
        if aux_acc is not None:
            aux_acc = jax.tree_util.tree_map(
                lambda g, d: g + last * d, aux_acc, aux_mb)
        loss_acc = loss_acc + last * loss_mb
        if collect_dx:
            dx_keep = jnp.where(b_active & (s == 0), dx_mb,
                                lax.dynamic_index_in_dim(dx_acc, m_bc,
                                                         axis=0,
                                                         keepdims=False))
            dx_acc = lax.dynamic_update_index_in_dim(dx_acc, dx_keep,
                                                     m_bc, axis=0)
        g_next = lax.ppermute(jnp.where(b_active, dx_mb, zero_act),
                              "stage", bwd_perm)
        return (a_next, g_next, resid, gparams, aux_acc, dx_acc,
                loss_acc, st), None

    resid0 = jnp.zeros((n_slots,) + x_mb.shape[1:], x_mb.dtype)
    dx0 = jnp.zeros_like(x_mb) if collect_dx else jnp.zeros((), x_mb.dtype)
    carry0 = (zero_act, zero_act, resid0, zero_params, zero_aux, dx0,
              jnp.zeros((), jnp.float32),
              state0 if stateful else jnp.zeros((), jnp.float32))
    ticks = jnp.arange(n_micro + 2 * (n_stages - 1))
    (_, _, _, gparams, aux_acc, dx_acc, loss_acc, st_fin), _ = lax.scan(
        tick, carry0, ticks)
    if stateful:
        return loss_acc, gparams, aux_acc, dx_acc, st_fin
    return loss_acc, gparams, aux_acc, dx_acc


def lm_1f1b_loss_and_grads(embed, block, mesh, n_micro, n_stages,
                           block_specs, act_spec, extra_axes,
                           params, ids, labels):
    """Loss + full grad dict for the embed/blocks/head LM family via the
    1F1B schedule — shared by PipelineParallelLM and ComposedParallelLM
    (they differ only in block forward, block specs, and activation
    sharding). The embedding runs outside the pipelined region with an
    explicit vjp; dx from the schedule closes its backward."""
    def embed_fwd(ep):
        emb, _ = embed.apply(ep, {}, ids)
        return emb
    emb, vjp_e = jax.vjp(embed_fwd, params["embed"])
    b, t, d = emb.shape
    mb = b // n_micro
    x_mb = emb.reshape(n_micro, mb, t, d)
    lab_mb = labels.reshape(n_micro, mb, t)
    run = one_f_one_b_schedule(block, n_micro, n_stages,
                               lm_head_loss(1.0 / (b * t)), extra_axes)
    loss, gblocks, ghead, dx_mb = shard_map(
        run, mesh=mesh,
        in_specs=(block_specs, P(), act_spec, act_spec),
        out_specs=(P(), block_specs, P(), act_spec),
        check_vma=False,
    )(params["blocks"], params["head"], x_mb, lab_mb)
    (dembed,) = vjp_e(dx_mb.reshape(b, t, d))
    return loss, {"embed": dembed, "blocks": gblocks, "head": ghead}


class PipelineParallelLM:
    """Decoder-only transformer LM trained with pipeline parallelism.

    Same architecture as ``models.transformer_lm`` (EmbeddingSequenceLayer
    + N TransformerBlocks + vocab head), but the block stack is sharded
    over the mesh ``stage`` axis and executed with the GPipe schedule.

    ids/labels: [B, T] int. B must divide into ``n_microbatches``
    microbatches; ``n_layers`` must divide by the stage-axis size.
    """

    def __init__(self, *, vocab_size, n_layers, d_model, n_heads, seq_len,
                 mesh: Mesh, n_microbatches=4, mlp_ratio=4, updater=None,
                 seed=12345, remat=False, schedule="gpipe"):
        assert "stage" in mesh.axis_names, "mesh needs a 'stage' axis"
        assert schedule in ("gpipe", "1f1b"), schedule
        self.vocab_size = vocab_size
        self.n_layers = n_layers
        self.d_model = d_model
        self.seq_len = seq_len
        self.mesh = mesh
        self.n_micro = n_microbatches
        self.n_stages = mesh.shape["stage"]
        assert n_layers % self.n_stages == 0, \
            f"{n_layers} layers not divisible into {self.n_stages} stages"
        self.embed = L.EmbeddingSequenceLayer(n_in=vocab_size, n_out=d_model,
                                              add_positional=True)
        self.block = L.TransformerBlock(
            n_out=d_model, mlp_ratio=mlp_ratio,
            mixer=L.MultiHeadAttention(n_out=d_model, n_heads=n_heads,
                                       causal=True))
        self.updater = updater or U.Adam(learning_rate=3e-4)
        self.seed = seed
        self.remat = remat
        self.schedule = schedule
        self.params = None
        self.opt_state = None
        self._step_fn = None
        self.iteration = 0

    # -- init ------------------------------------------------------------
    def init(self, rng=None):
        key = rng if rng is not None else jax.random.PRNGKey(self.seed)
        ke, kh, *kb = jax.random.split(key, 2 + self.n_layers)
        it = I.RecurrentType(self.d_model, self.seq_len)
        embed_p = self.embed.init(ke, I.RecurrentType(1, self.seq_len))
        blocks = [self.block.init(k, it) for k in kb]
        stacked = stack_blocks(blocks)
        head_p = {
            "W": jax.random.normal(kh, (self.d_model, self.vocab_size),
                                   jnp.float32) / np.sqrt(self.d_model),
            "b": jnp.zeros((self.vocab_size,), jnp.float32),
        }
        params = {"embed": embed_p, "blocks": stacked, "head": head_p}
        self.param_shardings = {
            "embed": jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), embed_p),
            "blocks": jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P("stage")), stacked),
            "head": jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), head_p),
        }
        self.params = jax.tree_util.tree_map(jax.device_put, params,
                                             self.param_shardings)
        opt = self.updater.init(self.params)
        # optimizer state mirrors param sharding (Adam m/v have param shapes)
        self.opt_state = jax.tree_util.tree_map(
            jax.device_put, opt, self._opt_shardings(opt))
        return self

    def _opt_shardings(self, opt_state):
        """Optimizer-state subtrees that mirror the param tree (Adam m/v,
        momentum buffers) take the param shardings wholesale; anything else
        replicates. Structure matching, not shape matching — two params
        sharing a shape must not steal each other's sharding."""
        p_struct = jax.tree_util.tree_structure(self.params)
        repl = NamedSharding(self.mesh, P())

        def per_entry(sub):
            if jax.tree_util.tree_structure(sub) == p_struct:
                return self.param_shardings
            return jax.tree_util.tree_map(lambda _: repl, sub)

        if isinstance(opt_state, dict):
            return {k: per_entry(v) for k, v in opt_state.items()}
        return per_entry(opt_state)

    # -- training --------------------------------------------------------
    def _loss_fn(self, params, ids, labels):
        emb, _ = self.embed.apply(params["embed"], {}, ids)
        b, t, d = emb.shape
        mb = b // self.n_micro
        x_mb = emb.reshape(self.n_micro, mb, t, d)
        run = gpipe_schedule(self.block, self.n_micro, self.n_stages,
                             remat=self.remat)
        piped = shard_map(
            run, mesh=self.mesh,
            in_specs=(P("stage"), P(None, "data")),
            out_specs=P(None, "data"),
            check_vma=False,
        )(params["blocks"], x_mb)
        h = piped.reshape(b, t, d)
        logits = h @ params["head"]["W"] + params["head"]["b"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                   axis=-1)
        return jnp.mean(nll)

    def _build_step_1f1b(self):
        """1F1B step: grads assembled from the explicit-VJP schedule
        (one_f_one_b_schedule) instead of differentiating the GPipe scan —
        loss and grads are the same math, the order (and the activation
        stash) changes."""
        upd = self.updater
        assert "data" in self.mesh.axis_names, \
            "PipelineParallelLM meshes carry a 'data' axis (size 1 is fine)"

        def step(params, opt_state, ids, labels, it):
            loss, grads = lm_1f1b_loss_and_grads(
                self.embed, self.block, self.mesh, self.n_micro,
                self.n_stages, P("stage"), P(None, "data"), ("data",),
                params, ids, labels)
            updates, opt_state = upd.update(grads, opt_state, params, it)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return params, opt_state, loss

        data_sh = NamedSharding(self.mesh, P("data"))
        opt_sh = self._opt_shardings(self.opt_state)
        return jax.jit(
            step,
            in_shardings=(self.param_shardings, opt_sh, data_sh, data_sh,
                          None),
            out_shardings=(self.param_shardings, opt_sh,
                           NamedSharding(self.mesh, P())),
            donate_argnums=(0, 1))

    def _build_step(self):
        if self.schedule == "1f1b":
            return self._build_step_1f1b()
        upd = self.updater

        def step(params, opt_state, ids, labels, it):
            loss, grads = jax.value_and_grad(self._loss_fn)(params, ids,
                                                            labels)
            updates, opt_state = upd.update(grads, opt_state, params, it)
            params = jax.tree_util.tree_map(jnp.add, params, updates)
            return params, opt_state, loss

        data_sh = NamedSharding(self.mesh, P("data"))
        opt_sh = self._opt_shardings(self.opt_state)
        return jax.jit(
            step,
            in_shardings=(self.param_shardings, opt_sh, data_sh, data_sh,
                          None),
            out_shardings=(self.param_shardings, opt_sh,
                           NamedSharding(self.mesh, P())),
            donate_argnums=(0, 1))

    def step(self, ids, labels):
        if self.params is None:
            self.init()
        if self._step_fn is None:
            self._step_fn = self._build_step()
        ids = _mesh.ensure_data_sharded(self.mesh, ids)
        labels = _mesh.ensure_data_sharded(self.mesh, labels)
        self.params, self.opt_state, loss = self._step_fn(
            self.params, self.opt_state, ids, labels, self.iteration)
        self.iteration += 1
        return loss

    # -- reference (for tests): same math, no pipeline -------------------
    def loss_reference(self, ids, labels):
        """Sequential forward with the SAME params on one device — the
        pipeline must match this exactly (it is the same computation)."""
        params = jax.device_get(self.params)
        emb, _ = self.embed.apply(params["embed"], {}, jnp.asarray(ids))

        def body(h, bp):
            y, _ = self.block.apply(bp, {}, h)
            return y, None
        h, _ = lax.scan(body, emb, params["blocks"])
        logits = h @ params["head"]["W"] + params["head"]["b"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(
            logp, jnp.asarray(labels)[..., None].astype(jnp.int32), axis=-1)
        return jnp.mean(nll)
