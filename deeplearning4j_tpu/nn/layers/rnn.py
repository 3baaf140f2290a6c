"""Recurrent layers: LSTM, GravesLSTM (peepholes), bidirectional, SimpleRnn,
RNN output/loss heads, LastTimeStep.

Reference analogs in /root/reference/deeplearning4j-nn/src/main/java/org/
deeplearning4j/nn/: layers/recurrent/LSTMHelpers.java:68 (activateHelper) /
:392 (backpropGradientHelper) shared by LSTM.java, GravesLSTM.java (peephole
connections), GravesBidirectionalLSTM.java; conf/layers/RnnOutputLayer.java.
The reference's fast path is CudnnLSTMHelper (fused cudnnRNN); the TPU-native
replacement is a single fused gate matmul per step inside lax.scan — x-side
projections for ALL timesteps are computed in one big MXU matmul outside the
scan, so the scan body only does the [B,H]x[H,4H] recurrent matmul.

Data layout: [batch, time, features] (batch-major); scan runs time-major
internally. Masking: a [batch, time] mask freezes state and zeroes output at
padded steps (reference: masking plumbed through activateHelper).

Gate order in the fused 4H axis: input (i), forget (f), cell candidate (g),
output (o).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import activations as _act
from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn import losses as _losses
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import ParamLayer, Layer
from deeplearning4j_tpu.utils import dtypes as _dtypes
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.utils.serde import register_config


@register_config
@dataclasses.dataclass(frozen=True)
class LSTM(ParamLayer):
    """params: Wx [nIn,4H], Wh [H,4H], b [4H]. forget_gate_bias init per
    reference default (GravesLSTM forgetGateBiasInit, typically 1.0)."""

    n_out: int = 0
    forget_gate_bias: float = 1.0
    gate_activation: object = "sigmoid"
    activation: object = dataclasses.field(default="tanh", kw_only=True)
    peephole: bool = False

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("Wx", "Wh", "Wp")
    BIAS_KEYS = ("b",)

    def output_type(self, input_type):
        assert isinstance(input_type, _inputs.RecurrentType), \
            f"{type(self).__name__} needs RNN input, got {input_type}"
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in, h = input_type.size, self.n_out
        k1, k2, k3 = jax.random.split(key, 3)
        b = jnp.zeros((4 * h,), dtype)
        b = b.at[h:2 * h].set(self.forget_gate_bias)  # forget-gate slice
        p = {
            "Wx": _init.init_weight(self.weight_init, k1, (n_in, 4 * h), n_in, h, dtype),
            "Wh": _init.init_weight(self.weight_init, k2, (h, 4 * h), h, h, dtype),
            "b": b,
        }
        if self.peephole:
            # diagonal peephole weights for i, f, o gates (GravesLSTM)
            p["Wp"] = 0.1 * jax.random.normal(k3, (3, h), dtype)
        return p

    def _step(self, params, carry, xz_t, mask_t):
        """One scan step. xz_t: precomputed x-projection [B, 4H]."""
        h_prev, c_prev = carry
        hsz = self.n_out
        z = xz_t + matmul(h_prev, params["Wh"])
        zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
        gate = _act.get(self.gate_activation)
        act = self.activation_fn()
        if self.peephole:
            wp = params["Wp"]
            zi = zi + wp[0] * c_prev
            zf = zf + wp[1] * c_prev
        i, f = gate(zi), gate(zf)
        g = act(zg)
        c = f * c_prev + i * g
        if self.peephole:
            zo = zo + params["Wp"][2] * c
        o = gate(zo)
        h = o * act(c)
        if mask_t is not None:
            m = mask_t[:, None].astype(h.dtype)
            h = m * h + (1 - m) * h_prev
            c = m * c + (1 - m) * c_prev
        return (h, c), h

    def _fused_eligible(self, x, mask):
        """Fused Pallas sequence kernel applies? (TPU backend only; the
        dispatch seam mirroring the reference's reflective cuDNN-helper
        loading at ConvolutionLayer.java:74-84 — here explicit.)"""
        from deeplearning4j_tpu.ops import lstm_pallas
        if not lstm_pallas.enabled():  # the TPU backend gate, one place
            return False
        return lstm_pallas.supported(
            x.shape, self.n_out, peephole=self.peephole, mask=mask,
            gate_activation=self.gate_activation
            if isinstance(self.gate_activation, str) else None,
            activation=self.activation
            if isinstance(self.activation, str) else None)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None,
              initial_state=None):
        b, t, _ = x.shape
        hsz = self.n_out
        # one big MXU matmul for all timesteps' input projections
        xz = matmul(x.reshape(b * t, -1), params["Wx"]) + params["b"]
        xz = xz.reshape(b, t, 4 * hsz).transpose(1, 0, 2)  # time-major
        mask_tm = None if mask is None else mask.transpose(1, 0)
        if initial_state is None:
            h0 = jnp.zeros((b, hsz), xz.dtype)
            c0 = jnp.zeros((b, hsz), xz.dtype)
        else:
            h0, c0 = initial_state

        if self._fused_eligible(x, mask):
            from deeplearning4j_tpu.ops.lstm_pallas import fused_sequence_padded
            # the kernel interface runs in the COMPUTE dtype (bf16 under the
            # mixed policy): halves the xz/dxz HBM traffic — the f32 dxz
            # stack alone was 38% of the train step in the round-2 profile —
            # and puts the recurrent matmul on the bf16 MXU path. Cell state
            # stays f32 inside the kernel. Masked batches ride the kernel
            # too (time-major [T, B] mask; state freezes at padded steps).
            cd, _ = _dtypes.compute_dtypes_for(x.dtype)
            wp = params.get("Wp")
            hs, (hT, cT) = fused_sequence_padded(
                xz.astype(cd), params["Wh"].astype(cd), h0.astype(cd),
                c0.astype(cd), wp=None if wp is None else wp.astype(cd),
                mask=mask_tm)
        elif mask_tm is None:
            def body(carry, xz_t):
                return self._step(params, carry, xz_t, None)
            (hT, cT), hs = lax.scan(body, (h0, c0), xz)
        else:
            def body(carry, inp):
                xz_t, m_t = inp
                return self._step(params, carry, xz_t, m_t)
            (hT, cT), hs = lax.scan(body, (h0, c0), (xz, mask_tm))
        y = hs.transpose(1, 0, 2)  # back to batch-major
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state

    def step_stateful(self, params, h_c, x_t):
        """Single-step inference API (reference: RecurrentLayer.rnnTimeStep)."""
        xz = matmul(x_t, params["Wx"]) + params["b"]
        return self._step(params, h_c, xz, None)

    def zero_carry(self, batch, dtype=jnp.float32):
        z = jnp.zeros((batch, self.n_out), dtype)
        return (z, z)

    def apply_with_carry(self, params, carry, x, *, mask=None):
        """Sequence apply that also returns the final (h, c) carry — the
        TBPTT building block (reference: rnnActivateUsingStoredState /
        doTruncatedBPTT at MultiLayerNetwork.java:1252-1254)."""
        b, t, _ = x.shape
        hsz = self.n_out
        xz = matmul(x.reshape(b * t, -1), params["Wx"]) + params["b"]
        xz = xz.reshape(b, t, 4 * hsz).transpose(1, 0, 2)
        mask_tm = None if mask is None else mask.transpose(1, 0)
        if carry is None:
            carry = self.zero_carry(b, xz.dtype)

        if mask_tm is None:
            def body(c, xz_t):
                return self._step(params, c, xz_t, None)
            final, hs = lax.scan(body, carry, xz)
        else:
            def body(c, inp):
                xz_t, m_t = inp
                return self._step(params, c, xz_t, m_t)
            final, hs = lax.scan(body, carry, (xz, mask_tm))
        y = hs.transpose(1, 0, 2)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, final


@register_config
@dataclasses.dataclass(frozen=True)
class GravesLSTM(LSTM):
    """LSTM with peephole connections (reference: GravesLSTM.java, after
    Graves 2013)."""

    peephole: bool = True


@register_config
@dataclasses.dataclass(frozen=True)
class SimpleRnn(ParamLayer):
    """Vanilla tanh RNN (reference: conf/layers/... BaseRecurrentLayer simple
    form). params: Wx [nIn,H], Wh [H,H], b [H]."""

    n_out: int = 0
    activation: object = dataclasses.field(default="tanh", kw_only=True)

    input_family = _inputs.RecurrentType

    WEIGHT_KEYS = ("Wx", "Wh")
    BIAS_KEYS = ("b",)

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in, h = input_type.size, self.n_out
        k1, k2 = jax.random.split(key)
        return {
            "Wx": _init.init_weight(self.weight_init, k1, (n_in, h), n_in, h, dtype),
            "Wh": _init.init_weight(self.weight_init, k2, (h, h), h, h, dtype),
            "b": jnp.zeros((h,), dtype),
        }

    def apply(self, params, state, x, *, train=False, rng=None, mask=None,
              initial_state=None):
        b, t, _ = x.shape
        act = self.activation_fn()
        xz = (matmul(x.reshape(b * t, -1), params["Wx"]) + params["b"]).reshape(b, t, -1)
        xz = xz.transpose(1, 0, 2)
        mask_tm = None if mask is None else mask.transpose(1, 0)
        h0 = initial_state if initial_state is not None else jnp.zeros((b, self.n_out), xz.dtype)

        def body(h_prev, inp):
            if mask_tm is None:
                xz_t, m_t = inp, None
            else:
                xz_t, m_t = inp
            h = act(xz_t + matmul(h_prev, params["Wh"]))
            if m_t is not None:
                m = m_t[:, None].astype(h.dtype)
                h = m * h + (1 - m) * h_prev
            return h, h

        _, hs = lax.scan(body, h0, xz if mask_tm is None else (xz, mask_tm))
        y = hs.transpose(1, 0, 2)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, state

    def zero_carry(self, batch, dtype=jnp.float32):
        return jnp.zeros((batch, self.n_out), dtype)

    def apply_with_carry(self, params, carry, x, *, mask=None):
        b = x.shape[0]
        if carry is None:
            carry = self.zero_carry(b, x.dtype)
        y, _ = self.apply(params, {}, x, mask=mask, initial_state=carry)
        # final hidden = last (mask-aware) output
        if mask is None:
            final = y[:, -1, :]
        else:
            idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
            final = y[jnp.arange(b), idx, :]
        return y, final


@register_config
@dataclasses.dataclass(frozen=True)
class Bidirectional(Layer):
    """Wrapper running a recurrent layer forward + backward over time.

    Reference: nn/conf/layers/recurrent Bidirectional wrapper &
    GravesBidirectionalLSTM.java. ``mode``: concat | add | mul | ave.
    Backward pass respects the mask by reversing only valid steps.
    """

    layer: object = None
    mode: str = "concat"

    input_family = _inputs.RecurrentType

    def output_type(self, input_type):
        inner = self.layer.output_type(input_type)
        if self.mode == "concat":
            return _inputs.RecurrentType(inner.size * 2, inner.timesteps)
        return inner

    def init(self, key, input_type, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        return {"fwd": self.layer.init(k1, input_type, dtype),
                "bwd": self.layer.init(k2, input_type, dtype)}

    def regularization_penalty(self, params):
        return (self.layer.regularization_penalty(params["fwd"]) +
                self.layer.regularization_penalty(params["bwd"]))

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        yf, _ = self.layer.apply(params["fwd"], {}, x, train=train, rng=rng, mask=mask)
        xr = jnp.flip(x, axis=1)
        mr = None if mask is None else jnp.flip(mask, axis=1)
        yb, _ = self.layer.apply(params["bwd"], {}, xr, train=train, rng=rng, mask=mr)
        yb = jnp.flip(yb, axis=1)
        if self.mode == "concat":
            y = jnp.concatenate([yf, yb], axis=-1)
        elif self.mode == "add":
            y = yf + yb
        elif self.mode == "mul":
            y = yf * yb
        elif self.mode == "ave":
            y = 0.5 * (yf + yb)
        else:
            raise ValueError(f"Unknown Bidirectional mode {self.mode!r}")
        return y, state


@register_config
@dataclasses.dataclass(frozen=True)
class GravesBidirectionalLSTM(Layer):
    """Convenience: Bidirectional(GravesLSTM) with concat output
    (reference: GravesBidirectionalLSTM.java)."""

    n_out: int = 0
    activation: object = "tanh"
    weight_init: object = "xavier"

    input_family = _inputs.RecurrentType

    def _inner(self):
        return Bidirectional(layer=GravesLSTM(n_out=self.n_out, activation=self.activation,
                                              weight_init=self.weight_init), mode="concat")

    def output_type(self, input_type):
        return self._inner().output_type(input_type)

    def init(self, key, input_type, dtype=jnp.float32):
        return self._inner().init(key, input_type, dtype)

    def regularization_penalty(self, params):
        return self._inner().regularization_penalty(params)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._inner().apply(params, state, x, train=train, rng=rng, mask=mask)


@register_config
@dataclasses.dataclass(frozen=True)
class RnnOutputLayer(ParamLayer):
    """Per-timestep dense + loss (reference: conf/layers/RnnOutputLayer.java).
    Applies [B,T,F]x[F,O] as one flattened MXU matmul. ``has_bias=False``
    leaves ``b`` out (a language-model head without one)."""

    n_out: int = 0
    loss: object = "mcxent"
    has_bias: bool = True
    activation: object = dataclasses.field(default="softmax", kw_only=True)

    input_family = _inputs.RecurrentType

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        n_in = input_type.size
        p = {"W": _init.init_weight(self.weight_init, key, (n_in, self.n_out),
                                    n_in, self.n_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def pre_output(self, params, x):
        b, t, f = x.shape
        z = matmul(x.reshape(b * t, f), params["W"])
        if self.has_bias:
            z = z + params["b"]
        return z.reshape(b, t, self.n_out)

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.activation_fn()(self.pre_output(params, x)), state

    def compute_loss(self, predictions, labels, mask=None):
        return _losses.get(self.loss)(predictions, labels, mask)


@register_config
@dataclasses.dataclass(frozen=True)
class RnnLossLayer(Layer):
    """Parameterless per-timestep loss (reference: conf/layers/RnnLossLayer.java)."""

    loss: object = "mcxent"
    activation: object = "identity"

    input_family = _inputs.RecurrentType

    def output_type(self, input_type):
        return input_type

    def apply(self, params, state, x, *, train=False, rng=None):
        return _act.get(self.activation)(x), state

    def compute_loss(self, predictions, labels, mask=None):
        return _losses.get(self.loss)(predictions, labels, mask)


@register_config
@dataclasses.dataclass(frozen=True)
class LastTimeStep(Layer):
    """Extract the last (mask-aware) timestep: [B,T,F] -> [B,F]
    (reference: conf/graph/rnn/LastTimeStepVertex.java)."""

    input_family = _inputs.RecurrentType

    def output_type(self, input_type):
        return _inputs.FeedForwardType(input_type.size)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if mask is None:
            return x[:, -1, :], state
        idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
        return x[jnp.arange(x.shape[0]), idx, :], state
