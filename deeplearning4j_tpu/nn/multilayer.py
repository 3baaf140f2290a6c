"""MultiLayerNetwork: the sequential-stack trainer.

Reference analog: nn/multilayer/MultiLayerNetwork.java (3225 LoC) —
fit(DataSetIterator):1205, calcBackpropGradients:1315, output:1993,
computeGradientAndScore:2255 — plus the Solver/StochasticGradientDescent/
BaseOptimizer stack (optimize/solvers/*, gradientAndScore at
BaseOptimizer.java:171, updater application at :187).

TPU-native design: instead of a mutable flat param buffer with per-layer views
mutated in place through a JNI boundary per op, the entire
forward+backward+update is ONE jitted XLA computation over a params pytree
(list of per-layer dicts). Donated buffers give the same zero-copy param update
the reference gets from views. The reference's workspace machinery
(MultiLayerNetwork.java:1221-1229) is subsumed by XLA's static buffer
allocation; its AsyncDataSetIterator prefetch is datasets/iterator.py.

The stateful-object API (fit/output/score) wraps the functional core
(init_fn/apply_fn/loss_fn/train_step) — use the functional core directly for
custom training loops or pjit sharding.
"""

from __future__ import annotations

import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import telemetry as _tm
from deeplearning4j_tpu.telemetry import health as _health
from deeplearning4j_tpu.nn import gradnorm as _gradnorm
from deeplearning4j_tpu.nn import losses as _losses
from deeplearning4j_tpu.nn import scopes as _scopes
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import base as _base
from deeplearning4j_tpu.utils import dtypes as _dtypes


def _accepts_mask(layer):
    try:
        return "mask" in inspect.signature(type(layer).apply).parameters
    except (ValueError, TypeError):
        return False


class MultiLayerNetwork:
    """Sequential network: config in, functional core + convenience API out."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layer_inputs, self.output_type = conf.layer_input_types()
        self._mask_aware = [_accepts_mask(l) for l in conf.layers]
        self.params = None
        self.state = None
        self.opt_state = None
        self.iteration = 0
        self.epoch = 0
        self.listeners = []
        self._train_step = None
        self._train_step_health = None
        self._rng = jax.random.PRNGKey(conf.seed)

    # ------------------------------------------------------------------
    # functional core
    # ------------------------------------------------------------------

    def init(self, rng=None, dtype=None):
        """Initialize params/state/opt_state. Returns (params, state)."""
        rng = self._rng if rng is None else rng
        dtype = dtype or _dtypes.get_policy().param_dtype
        params, state = [], []
        with _tm.span("net.init"):
            for layer, in_type in zip(self.conf.layers, self.layer_inputs):
                rng, sub = jax.random.split(rng)
                params.append(layer.init(sub, in_type, dtype))
                state.append(layer.init_state(in_type, dtype))
            self._check_ties(params)
            self.params, self.state = params, state
            self.opt_state = self.conf.updater.init(params)
        return params, state

    def _check_ties(self, params):
        for tie in self.conf.ties:
            if tie.source_name not in params[tie.source_layer]:
                raise ValueError(
                    f"{tie}: layer {tie.source_layer} has no parameter "
                    f"{tie.source_name!r} (has "
                    f"{sorted(params[tie.source_layer])})")
            if tie.name in params[tie.layer]:
                raise ValueError(
                    f"{tie}: layer {tie.layer} makes a parameter "
                    f"{tie.name!r} of its own; a tied parameter has one "
                    "owner")

    def _tied(self, params):
        """``params`` as the layers read them: every ``ParamTie`` of the
        configuration resolved, the reading layer's dict holding the
        owner's leaf under the tie's name. The tree itself (what the
        updater, ``num_params()`` and a checkpoint see) holds the leaf
        once; autodiff sums its uses' gradients onto it."""
        if not self.conf.ties:
            return params
        out = list(params)
        for tie in self.conf.ties:
            out[tie.layer] = {
                **out[tie.layer],
                tie.name: params[tie.source_layer][tie.source_name]}
        return out

    def apply_fn(self, params, state, x, *, train=False, rng=None, mask=None,
                 layer_limit=None, logits=False):
        """Forward pass. Returns (output, new_state); with ``logits`` the
        last layer hands out its pre-activation output instead."""
        new_state = list(state)
        params = self._tied(params)
        cur_type = self.conf.input_type
        n = len(self.conf.layers) if layer_limit is None else layer_limit
        for i in range(n):
            x, new_state[i], rng, cur_type = self._apply_layer(
                i, params[i], state[i], x, cur_type, train=train, rng=rng,
                mask=mask, logits=logits and i == n - 1)
        return x, new_state

    def _apply_layer(self, i, layer_params, state_i, x, cur_type, *, train,
                     rng, mask, logits=False):
        """ONE layer of the forward loop — the definition ``apply_fn``
        iterates and the ZeRO-3 streamed-gather scan body reuses
        (parallel/data_parallel._streamed_loss runs it inside a
        ``lax.scan`` over the stacked trunk slab, so the adapt / input
        dropout / rng-split / weight-noise / remat order here IS the
        bit-exactness contract between the two paths). Returns
        ``(y, new_state_i, rng, next_type)``."""
        layer = self.conf.layers[i]
        with _scopes.layer(i, layer):
            # FrozenLayer.java:23 contract: a frozen layer "behaves as the
            # layer within it would during TEST regardless of the
            # training/test mode" — frozen BN normalizes with its running
            # statistics and does NOT update them; frozen dropout is off
            l_train = train and i not in set(
                getattr(self, "frozen_layers", ()))
            fam = layer.input_family
            if fam is not None and not isinstance(cur_type, fam):
                x = _inputs.adapt(x, cur_type, fam)
                cur_type = _inputs.adapted_type(cur_type, fam)
            if l_train and layer.dropout > 0.0 and rng is not None:  # graftlint: disable=R2 -- layer is conf metadata picked by a Python int index, never a tracer
                rng, sub = jax.random.split(rng)
                from deeplearning4j_tpu.nn.layers.base import dropout_mask
                x = dropout_mask(sub, x, layer.dropout)
            kwargs = {}
            if self._mask_aware[i] and mask is not None \
                    and mask.ndim >= 2:
                # a 1-d mask is an example-validity mask (shape
                # bucketing): it has no timestep info to forward into
                # mask-aware layers, which require [batch, time]
                kwargs["mask"] = mask
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            wn = getattr(layer, "weight_noise", None)
            if l_train and wn is not None and sub is not None \
                    and layer_params:
                sub, noise_rng = jax.random.split(sub)
                layer_params = wn.perturb(noise_rng, layer, layer_params)

            def run(p, s, xx, r, _layer=layer, _kwargs=kwargs,
                    _train=l_train):
                if logits:
                    return _layer.pre_output(p, xx), s
                return _layer.apply(p, s, xx, train=_train, rng=r, **_kwargs)

            if self.conf.gradient_checkpointing:
                # remat: drop this layer's activations after the forward and
                # recompute them during backprop — HBM for FLOPs
                run = jax.checkpoint(run)
            y, new_state_i = run(layer_params, state_i, x, sub)
            return y, new_state_i, rng, layer.output_type(cur_type)

    def loss_fn(self, params, state, x, y, *, train=True, rng=None, mask=None,
                label_mask=None):
        """Score = output-layer loss + L1/L2 penalties (reference:
        computeGradientAndScore at MultiLayerNetwork.java:2255 + calcL1/calcL2).
        Returns (loss, (new_state, predictions))."""
        out_layer = self.conf.layers[-1]

        def loss_mask(new_state):
            """The mask the loss reads: the fed label mask, else the one a
            layer handed on (``pop_loss_mask``), else the fed mask."""
            handed, new_state = _base.pop_loss_mask(new_state)
            if label_mask is not None:
                return label_mask, new_state
            return (mask if handed is None else handed), new_state

        if hasattr(out_layer, "loss_from_features"):
            # center-loss style heads need their input features for the loss
            feats, new_state = self.apply_fn(params, state, x, train=train,
                                             rng=rng, mask=mask,
                                             layer_limit=len(self.conf.layers) - 1)
            lm, new_state = loss_mask(new_state)
            with jax.named_scope("loss"):
                loss, preds, out_state = out_layer.loss_from_features(
                    self._tied(params)[-1], state[-1], feats, y, lm,
                    train=train)
            new_state = list(new_state)
            new_state[-1] = out_state
        else:
            if not hasattr(out_layer, "compute_loss"):
                raise ValueError("Last layer must be an output/loss layer, got "
                                 f"{type(out_layer).__name__}")
            # a softmax head under a cross-entropy takes its loss from its
            # logits; the probabilities are then for the caller, and dead
            # code in a train step
            logits_loss = _losses.from_logits(out_layer)
            preds, new_state = self.apply_fn(
                params, state, x, train=train, rng=rng, mask=mask,
                logits=logits_loss is not None)
            lm, new_state = loss_mask(new_state)
            with jax.named_scope("loss"):
                loss = (logits_loss or out_layer.compute_loss)(preds, y, lm)
            if logits_loss is not None:
                preds = out_layer.activation_fn()(preds)
        with jax.named_scope("loss"):
            for layer, p in zip(self.conf.layers, params):
                if p:
                    loss = loss + layer.regularization_penalty(p)
            loss, new_state = _base.pop_aux_losses(loss, new_state)
        return loss, (new_state, preds)

    # ------------------------------------------------------------------
    # truncated BPTT (reference: doTruncatedBPTT, MultiLayerNetwork.java:
    # 1252-1254 + BackpropType.TruncatedBPTT) — long sequences are split
    # into tbptt_fwd_length chunks; RNN hidden state carries across chunks
    # with stop_gradient at the boundary, bounding the backprop window.
    # ------------------------------------------------------------------

    def _apply_rnn(self, params, state, x, carries, *, train=False, rng=None,
                   mask=None, logits=False):
        """Forward pass threading RNN carries. Returns (y, new_state,
        new_carries); with ``logits`` the last layer hands out its
        pre-activation output, as in ``apply_fn``."""
        new_state = list(state)
        new_carries = list(carries)
        params = self._tied(params)
        cur_type = self.conf.input_type
        last = len(self.conf.layers) - 1
        for i, layer in enumerate(self.conf.layers):
            with _scopes.layer(i, layer):
                fam = layer.input_family
                if fam is not None and not isinstance(cur_type, fam):
                    x = _inputs.adapt(x, cur_type, fam)
                    cur_type = _inputs.adapted_type(cur_type, fam)
                if rng is not None:
                    rng, sub = jax.random.split(rng)
                else:
                    sub = None
                if hasattr(layer, "apply_with_carry"):
                    x, new_carries[i] = layer.apply_with_carry(
                        params[i], carries[i], x, mask=mask)
                elif logits and i == last:
                    x = layer.pre_output(params[i], x)
                else:
                    kwargs = ({"mask": mask} if self._mask_aware[i]
                              and mask is not None else {})
                    x, new_state[i] = layer.apply(
                        params[i], state[i], x, train=train, rng=sub,
                        **kwargs)
            cur_type = layer.output_type(cur_type)
        return x, new_state, new_carries

    def make_tbptt_step(self, jit=True):
        conf = self.conf
        _base.refuse_loss_mask_layers(conf.layers, "the truncated-BPTT step")

        def tbptt_step(params, state, opt_state, carries, x, y, step, rng, mask=None):
            carries = jax.tree_util.tree_map(jax.lax.stop_gradient, carries)

            def chunk_loss(params):
                out_layer = conf.layers[-1]
                logits_loss = _losses.from_logits(out_layer)
                out, new_state, new_carries = self._apply_rnn(
                    params, state, x, carries, train=True, rng=rng, mask=mask,
                    logits=logits_loss is not None)
                with jax.named_scope("loss"):
                    loss = (logits_loss or out_layer.compute_loss)(out, y,
                                                                   mask)
                    for layer, p in zip(conf.layers, params):
                        if p:
                            loss = loss + layer.regularization_penalty(p)
                    loss, new_state = _base.pop_aux_losses(loss, new_state)
                return loss, (new_state, new_carries)

            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                chunk_loss, has_aux=True)(params)
            with jax.named_scope("grad_norm"):
                grads = _gradnorm.normalize_grads(
                    conf.gradient_normalization, grads,
                    conf.gradient_normalization_threshold)
            with jax.named_scope("updater"):
                updates, new_opt = conf.updater.update(grads, opt_state,
                                                       params, step)
                new_params = jax.tree_util.tree_map(lambda p, u: p + u,
                                                    params, updates)
            return new_params, new_state, new_opt, new_carries, loss

        return jax.jit(_scopes.stamped(tbptt_step)) if jit else tbptt_step

    def _fit_tbptt(self, x, y, mask):
        if not hasattr(self, "_tbptt_step") or self._tbptt_step is None:
            self._tbptt_step = self.make_tbptt_step()
        T = x.shape[1]
        L = self.conf.tbptt_fwd_length
        carries = [l.zero_carry(x.shape[0], jnp.asarray(x).dtype)
                   if hasattr(l, "zero_carry") else None
                   for l in self.conf.layers]
        total = 0.0
        n_chunks = 0
        for t0 in range(0, T, L):
            cx = jnp.asarray(x[:, t0:t0 + L])
            cy = jnp.asarray(y[:, t0:t0 + L])
            cm = jnp.asarray(mask[:, t0:t0 + L]) if mask is not None else None
            self._rng, sub = jax.random.split(self._rng)
            (self.params, self.state, self.opt_state, carries, loss) = \
                self._tbptt_step(self.params, self.state, self.opt_state,
                                 carries, cx, cy, self.iteration, sub, cm)
            # accumulate ON DEVICE: a per-chunk float(loss) would pay one
            # host round-trip per TBPTT chunk and serialize dispatch
            total = total + loss
            n_chunks += 1
            self.iteration += 1
        self.score_value = float(total) / max(n_chunks, 1)
        return self.score_value

    # ------------------------------------------------------------------
    # streaming inference (reference: RecurrentLayer.rnnTimeStep contract)
    # ------------------------------------------------------------------

    def rnn_clear_previous_state(self):
        self._rnn_stream_state = None

    def rnn_time_step(self, x):
        """One timestep [B, F] (or a short [B,T,F] chunk) of streaming
        inference, carrying hidden state between calls."""
        x = jnp.asarray(x)
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        carries = getattr(self, "_rnn_stream_state", None)
        if carries is None:
            carries = [l.zero_carry(x.shape[0], x.dtype)
                       if hasattr(l, "zero_carry") else None
                       for l in self.conf.layers]
        y, _, carries = self._apply_rnn(self.params, self.state, x, carries,
                                        train=False)
        self._rnn_stream_state = carries
        return y[:, 0] if squeeze else y

    def compute_gradients(self, params, state, x, y, *, rng=None, mask=None):
        """Loss + normalized/clipped gradients (reference:
        computeGradientAndScore + gradient normalization inside
        updateGradientAccordingToParams). Returns (loss, new_state, grads).
        The distributed masters insert their gradient exchange between this
        and apply_update."""
        (loss, (new_state, _)), grads = jax.value_and_grad(
            self.loss_fn, has_aux=True)(params, state, x, y, train=True,
                                        rng=rng, mask=mask)
        with jax.named_scope("grad_norm"):
            grads = _gradnorm.normalize_grads(
                self.conf.gradient_normalization, grads,
                self.conf.gradient_normalization_threshold)
        return loss, new_state, grads

    def apply_update(self, params, opt_state, grads, step):
        """updater -> parameter add -> constraints (reference:
        BaseOptimizer.java:187 -> StochasticGradientDescent step :78 ->
        applyConstraints :97)."""
        with jax.named_scope("updater"):
            updates, new_opt = self.conf.updater.update(grads, opt_state,
                                                        params, step)
            new_params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                                updates)
            return self.apply_constraints(new_params, step), new_opt

    def apply_constraints(self, params, step):
        """The constraint pass of apply_update, exposed separately for
        update paths that run the updater elsewhere (the distributed
        masters' sharded weight update applies the updater to flat
        1/w shards, then constrains the reassembled params HERE — one
        definition, no drift)."""
        return [l.apply_constraints(p, step, 0) if p else p
                for l, p in zip(self.conf.layers, params)]

    def make_train_step(self, donate=True, jit=True, with_health=False):
        """Build the jitted train step:
        (params, state, opt_state, x, y, step, rng, mask) ->
        (params, state, opt_state, loss[, health]).

        Mirrors BaseOptimizer.gradientAndScore:171 -> updater :187 ->
        StochasticGradientDescent step :78, fused into one XLA computation.
        ``with_health=True`` appends the numerics-watchdog scalar bundle
        (telemetry/health.py) — a few extra fused reductions, fetched
        asynchronously by the fit loop's HealthMonitor.
        """
        def train_step(params, state, opt_state, x, y, step, rng, mask=None):
            loss, new_state, grads = self.compute_gradients(
                params, state, x, y, rng=rng, mask=mask)
            if with_health:
                with jax.named_scope("health"):
                    health = _health.health_stats(grads, params, loss)
            new_params, new_opt = self.apply_update(params, opt_state, grads,
                                                    step)
            if with_health:
                return new_params, new_state, new_opt, loss, health
            return new_params, new_state, new_opt, loss

        if not jit:
            return train_step
        donate_argnums = (0, 1, 2) if donate else ()
        return jax.jit(_scopes.stamped(train_step),
                       donate_argnums=donate_argnums)

    def make_train_steps(self, k, donate=True, jit=True, with_health=False):
        """Fused K-step engine: ONE dispatch runs K train steps under
        ``jax.lax.scan`` over a stacked ``[K, B, ...]`` super-batch, the
        iteration counter and RNG chain carried on device (nn/fused.py;
        ``fit(steps_per_dispatch=K)`` drives it)."""
        from deeplearning4j_tpu.nn import fused as _fused
        return _fused.make_train_steps(self, k, donate=donate, jit=jit,
                                       with_health=with_health)

    # ------------------------------------------------------------------
    # convenience (stateful) API
    # ------------------------------------------------------------------

    def fit(self, data, labels=None, *, epochs=1, batch_size=None, mask=None,
            steps_per_dispatch=1, pad_ragged=None):
        """Train. ``data`` is either (features, labels) arrays or an iterator
        yielding dicts/tuples per minibatch (reference: fit(DataSetIterator)
        at MultiLayerNetwork.java:1205).

        ``steps_per_dispatch=K`` (default 1 = this loop, unchanged) runs K
        steps per device dispatch through the fused ``lax.scan`` engine
        (nn/fused.py): super-batches of K minibatches are stacked +
        ``device_put`` on a prefetch thread while the current dispatch
        runs, ragged batch/K-tail shapes are bucketed with validity masks
        (exact; ``recompiles_total`` stays flat), and scores/health come
        back one dispatch late as stacked arrays.

        ``pad_ragged=True`` applies the same shape bucketing to the K=1
        loop: every batch padded to one compiled shape with the validity
        folded into the loss mask, so the ragged tail batch of each epoch
        stops costing a fresh XLA compile."""
        if self.params is None:
            self.init()
        k = int(steps_per_dispatch)
        if k > 1:
            if self.conf.backprop_type == "tbptt":
                # reject only when TBPTT could actually engage (the K=1
                # loop gates it per batch: 3-d input with T > fwd_length,
                # the ComputationGraph.fit convention); feature arrays
                # short enough — or non-temporal — train fused fine
                pair = labels is None and isinstance(data, (tuple, list))
                feats = data[0] if pair else data
                labs = data[1] if pair else labels
                safe = (hasattr(feats, "shape") and
                        (feats.ndim != 3
                         or feats.shape[1] <= self.conf.tbptt_fwd_length
                         or (hasattr(labs, "shape") and labs.ndim != 3)))
                if not safe:
                    raise ValueError(
                        "steps_per_dispatch > 1 does not compose with "
                        "TBPTT (the chunk loop is its own on-device "
                        "scan); use the default single-step path")
            from deeplearning4j_tpu.nn import fused as _fused
            return _fused.fit_fused(
                self,
                lambda: self._batches(data, labels, batch_size, mask),
                epochs=epochs, k=k, batch_size=batch_size)
        # the K=1 loop is the shared StepDriver (continuous/driver.py):
        # the identical pipelined body (one-step-late score fetch via
        # ScorePipeline — no per-iteration float(loss) sync, graftlint R1
        # — one-late health bundles, trace handoff, flight records), now
        # resumable between rounds for the continuous-learning tier. The
        # per-batch TBPTT hook preserves the historical contract: a long
        # 3-d sequence batch runs the chunked on-device scan instead.
        from deeplearning4j_tpu.continuous.driver import StepDriver
        conf = self.conf

        def tbptt_fn(x, y):
            return (conf.backprop_type == "tbptt" and x.ndim == 3
                    and y.ndim == 3
                    and x.shape[1] > conf.tbptt_fwd_length)

        drv = StepDriver(
            self,
            lambda: self._batches(data, labels, batch_size, mask,
                                  pad_to=True if pad_ragged else None),
            tbptt_fn=tbptt_fn)
        return drv.run(epochs)

    def _batches(self, data, labels, batch_size, mask, pad_to=None):
        from deeplearning4j_tpu.datasets.iterator import iter_batches
        yield from iter_batches(data, labels, batch_size, mask,
                                pad_to=pad_to)

    def output(self, x, train=False, mask=None):
        """Inference forward pass (reference: MultiLayerNetwork.output:1993)."""
        if self.params is None:
            self.init()
        out, _ = self._jitted_apply()(self.params, self.state, jnp.asarray(x),
                                      mask if mask is None else jnp.asarray(mask))
        return out

    @functools.lru_cache(maxsize=1)
    def _jitted_apply(self):
        def fwd(params, state, x, mask):
            return self.apply_fn(params, state, x, train=False, mask=mask)
        return jax.jit(fwd)

    def feed_forward(self, x, train=False):
        """All intermediate activations (reference: feedForwardToLayer:2286)."""
        acts = []
        x = jnp.asarray(x)
        cur_type = self.conf.input_type
        state = list(self.state)
        params = self._tied(self.params)
        for i, layer in enumerate(self.conf.layers):
            fam = layer.input_family
            if fam is not None and not isinstance(cur_type, fam):
                x = _inputs.adapt(x, cur_type, fam)
                cur_type = _inputs.adapted_type(cur_type, fam)
            x, state[i] = layer.apply(params[i], state[i], x, train=train)
            cur_type = layer.output_type(cur_type)
            acts.append(x)
        return acts

    def score(self, x, y, mask=None):
        if self.params is None:
            self.init()
        loss, _ = self.loss_fn(self.params, self.state, jnp.asarray(x),
                               jnp.asarray(y), train=False, mask=mask)
        return float(loss)

    def predict(self, x, mask=None):
        """Predicted class indices [batch] (reference:
        MultiLayerNetwork.predict(INDArray) at MultiLayerNetwork.java:
        the argmax convenience over output())."""
        out = np.asarray(self.output(x, mask=mask))
        return np.argmax(out, axis=-1)

    def f1_score(self, x, y, mask=None):
        """Macro F1 over a labelled batch (reference: the Classifier
        interface's f1Score entry). A label mask excludes padded
        timesteps/examples from the tally, matching evaluate()'s
        iterator path."""
        from deeplearning4j_tpu.eval.classification import Evaluation
        e = Evaluation()
        out = self.output(x, mask=mask)
        e.eval(np.asarray(y), np.asarray(out),
               mask=None if mask is None else np.asarray(mask))
        return e.f1()

    def evaluate(self, data, labels=None, *, batch_size=None,
                 evaluation=None):
        """Classification Evaluation over arrays, an (x, y) pair, or any
        DataSetIterator (reference: MultiLayerNetwork.evaluate(
        DataSetIterator) at MultiLayerNetwork.java:2621 — the API every
        reference example ends with: ``print(net.evaluate(it).stats())``).
        Pass ``evaluation=`` to accumulate into an existing instance
        (e.g. a cost-array or top-N one)."""
        from deeplearning4j_tpu.datasets.iterator import iter_batches
        from deeplearning4j_tpu.eval.classification import Evaluation

        e = evaluation if evaluation is not None else Evaluation()
        for bx, by, bm in iter_batches(data, labels, batch_size, None):
            out = self.output(bx, mask=bm)
            e.eval(np.asarray(by), np.asarray(out),
                   mask=None if bm is None else np.asarray(bm))
        return e

    def evaluate_regression(self, data, labels=None, *, batch_size=None):
        """RegressionEvaluation over the same input shapes (reference:
        MultiLayerNetwork.evaluateRegression)."""
        from deeplearning4j_tpu.datasets.iterator import iter_batches
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation

        e = RegressionEvaluation()
        for bx, by, bm in iter_batches(data, labels, batch_size, None):
            e.eval(np.asarray(by), np.asarray(self.output(bx, mask=bm)),
                   mask=None if bm is None else np.asarray(bm))
        return e

    def evaluate_roc(self, data, labels=None, *, batch_size=None,
                     threshold_steps=0):
        """ROC (binary) or ROCMultiClass over the same input shapes
        (reference: MultiLayerNetwork.evaluateROC / evaluateROCMultiClass)."""
        from deeplearning4j_tpu.datasets.iterator import iter_batches
        from deeplearning4j_tpu.eval.roc import ROC, ROCMultiClass

        roc = None
        for bx, by, bm in iter_batches(data, labels, batch_size, None):
            out = np.asarray(self.output(bx, mask=bm))
            if roc is None:
                binary = out.shape[-1] <= 2
                roc = (ROC(threshold_steps) if binary
                       else ROCMultiClass(threshold_steps))
            roc.eval(np.asarray(by), out,
                     mask=None if bm is None else np.asarray(bm))
        if roc is None:
            raise ValueError("no data to evaluate")
        return roc

    def num_params(self):
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params))

    def add_listener(self, *ls):
        self.listeners.extend(ls)
        return self
