"""Layer protocol.

Reference analog: the conf/impl split in dl4j (nn/conf/layers/*.java configs +
nn/layers/*.java implementations, /root/reference/deeplearning4j-nn). In the
TPU-native design a layer IS its config: a frozen dataclass carrying
hyperparameters plus pure functions

    output_type(input_type)                  -> InputType      (shape inference)
    init(key, input_type, dtype)             -> params dict    (pytree leaf dicts)
    init_state(input_type, dtype)            -> state dict     (e.g. BN running stats)
    apply(params, state, x, *, train, rng)   -> (y, new_state)

There is no mutable object state: parameters and mutable statistics live in
pytrees threaded by the network, so the whole forward/backward is jit-compiled
in one XLA computation (the reference instead crosses JVM->JNI per op).

Regularization fields (l1/l2/dropout/constraints) are consumed by the network:
l1/l2 are added to the loss over this layer's regularizable params
(reference: BaseLayer.calcL1/calcL2), dropout is applied to the layer INPUT
during training (reference: BaseLayer.applyDropOutIfNecessary semantics, with
inverted scaling), constraints are projections applied post-update.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations as _act
from deeplearning4j_tpu.nn.conf import inputs as _inputs


@dataclasses.dataclass(frozen=True)
class Layer:
    """Base: a parameterless layer. Fields are hyperparameters only."""

    name: str | None = dataclasses.field(default=None, kw_only=True)
    dropout: float = dataclasses.field(default=0.0, kw_only=True)  # drop probability on layer input

    # which input family this layer consumes; the network auto-adapts
    input_family = _inputs.FeedForwardType

    def output_type(self, input_type):
        return input_type

    def init(self, key, input_type, dtype=jnp.float32):
        return {}

    def init_state(self, input_type, dtype=jnp.float32):
        return {}

    def apply(self, params, state, x, *, train=False, rng=None):
        return x, state

    # ---- regularization hooks consumed by the network ----
    def regularization_penalty(self, params):
        return 0.0

    def apply_constraints(self, params, iteration, epoch):
        return params


@dataclasses.dataclass(frozen=True)
class ParamLayer(Layer):
    """Base for layers with weights: activation + init + L1/L2 + constraints."""

    activation: object = dataclasses.field(default="identity", kw_only=True)
    weight_init: object = dataclasses.field(default="xavier", kw_only=True)
    bias_init: float = dataclasses.field(default=0.0, kw_only=True)
    l1: float = dataclasses.field(default=0.0, kw_only=True)
    l2: float = dataclasses.field(default=0.0, kw_only=True)
    l1_bias: float = dataclasses.field(default=0.0, kw_only=True)
    l2_bias: float = dataclasses.field(default=0.0, kw_only=True)
    constraints: tuple = dataclasses.field(default=(), kw_only=True)
    weight_noise: object = dataclasses.field(default=None, kw_only=True)

    WEIGHT_KEYS = ("W",)
    BIAS_KEYS = ("b",)

    def activation_fn(self):
        return _act.get(self.activation)

    def regularization_penalty(self, params):
        """L1/L2 on weights, separate coefficients for biases (reference:
        BaseLayer.calcL1/calcL2 exclude biases unless l1Bias/l2Bias set)."""
        pen = 0.0
        for k, v in params.items():
            if k in self.BIAS_KEYS:
                if self.l1_bias:
                    pen = pen + self.l1_bias * jnp.sum(jnp.abs(v))
                if self.l2_bias:
                    pen = pen + 0.5 * self.l2_bias * jnp.sum(v * v)
            else:
                if self.l1:
                    pen = pen + self.l1 * jnp.sum(jnp.abs(v))
                if self.l2:
                    pen = pen + 0.5 * self.l2 * jnp.sum(v * v)
        return pen

    def apply_constraints(self, params, iteration, epoch):
        out = params
        for c in self.constraints:
            out = c.apply(self, out, iteration, epoch)
        return out


def pop_aux_losses(loss, states):
    """(loss + popped aux terms, cleaned states).

    Contract for input-dependent layer losses (MoE load balancing): a layer
    stashes the term in its per-step state under ``"aux_loss"``; the
    container's loss function pops it here so the PERSISTENT state structure
    stays stable across steps (jit/scan/donation invariant). ``states`` is a
    list of per-layer dicts (MultiLayerNetwork) or a dict keyed by vertex
    name (ComputationGraph).
    """
    items = (list(states.items()) if isinstance(states, dict)
             else list(enumerate(states)))
    out = dict(states) if isinstance(states, dict) else list(states)
    for k, s in items:
        if isinstance(s, dict) and "aux_loss" in s:
            s = dict(s)
            loss = loss + s.pop("aux_loss")
            out[k] = s
    return loss, out


def pop_loss_mask(states):
    """(the loss mask a layer handed on or None, cleaned states).

    DL4J's ``Layer.feedForwardMaskArray`` contract in its ``Passthrough``
    state, by ``pop_aux_losses``' mechanism: a layer that makes the
    per-position weights of the loss inside the network (a
    block-diffusion input layer: which tokens it masked, over their noise
    level) stashes them in its per-step state under ``"loss_mask"``; the
    layers after it do not apply them, and the container's loss function
    pops them here and hands them to the output layer where no label mask
    is fed. The last layer that stashed one wins. ``states`` is a list of
    per-layer dicts (MultiLayerNetwork)."""
    out, mask = list(states), None
    for i, s in enumerate(states):
        if isinstance(s, dict) and "loss_mask" in s:
            s = dict(s)
            mask = s.pop("loss_mask")
            out[i] = s
    return mask, out


def refuse_loss_mask_layers(layers, who):
    """Raise where a layer of ``layers`` hands the loss its weights
    (``hands_loss_mask``: it stashes ``loss_mask``) and ``who`` is a loss
    path that does not pop them: the weights would be dropped and the key
    would stay in the carried state."""
    for layer in layers:
        if getattr(layer, "hands_loss_mask", False):
            raise ValueError(
                f"{type(layer).__name__} hands the loss its per-position "
                f"weights, which {who} does not read; train it through "
                "MultiLayerNetwork.fit / make_train_step")


def dropout_mask(rng, x, rate):
    """Inverted dropout: scale retained units by 1/(1-rate)."""
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)
