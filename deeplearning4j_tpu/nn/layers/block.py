"""The one block of the language models: norm -> mixer -> residual, norm ->
FFN -> residual. The mixer is a layer object the block is given
(nn/layers/attention.py, nn/layers/mixers/); this module imports none of
them. The FFN half (dense, gated, routed experts) is still the block's own
(ROADMAP D22)."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import initializers as _init
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.nn.layers.core import matmul
from deeplearning4j_tpu.nn.layers.norms import LayerNormalization, RMSNorm
from deeplearning4j_tpu.utils.serde import register_config


@register_config
@dataclasses.dataclass(frozen=True)
class TransformerBlock(Layer):
    """A pre-norm residual block of two halves over [B,T,F]:
    ``x <- x + mixer(norm(x))``, then ``x <- x + ffn(norm(x))``.

    ``mixer`` is a layer object that mixes over time
    (``MultiHeadAttention``, or one of ``nn/layers/mixers/``): a
    ``ParamLayer`` of output width ``n_out`` whose ``apply`` takes
    ``mask=`` and whose class says under which key the block keeps its
    parameters (``param_key``). Its configuration is complete where it is
    written; the block passes it nothing and knows nothing else of it.
    ``ffn`` is "mlp" (``mlp_ratio`` x ``activation``, biased with
    ``bias``) | "gated" (``act(x Wg) * (x Wu)`` then ``Wd``) | "moe", of
    width ``ffn_width`` (None = ``n_out * mlp_ratio``). **Either half may
    be absent**: ``mixer=None`` is norm -> FFN -> residual alone,
    ``ffn="none"`` norm -> mixer -> residual alone (the single-part layers
    of the Nemotron-H family); the absent half has no norm, no
    parameters, no scope and no residual add. ``norm`` "layer" | "rms"
    (``norm_eps``, None = the norm's own default; ``norm_zero_centered``
    the RMS gains about zero); ``sandwich`` adds a norm after each half,
    before its residual add (``ln1_post`` / ``ln2_post``).

    ``"moe"`` is a dropless top-``top_k`` router (``router`` "sigmoid" |
    "softmax") over ``n_experts`` experts of that width, gated or, with
    ``expert_gated=False``, ungated (``act(x Wu)`` then ``Wd``, no
    ``moe_Wg``), of which this layer holds ``experts_held`` = (first, end)
    (() = all) and computes their part of the result
    (``moe.routed_experts``), times ``routed_scale``; the last step's
    ``moe_load`` / ``moe_elsewhere`` and the sigmoid router's
    ``expert_bias`` live in the layer's state. ``shared_expert_width`` > 0
    adds a shared expert of that width and the experts' form over every
    token, times ``sigmoid(x w_sg)`` unless ``shared_expert_gate=False``
    (``moe_shared_*``; held whole whatever ``experts_held`` says).
    ``recompute_moe`` keeps nothing of the routed experts' part for the
    backward pass but its inputs (``jax.checkpoint``): the sorted buffers,
    sized for all ``N k`` assignments, are made again there, which changes
    what is kept and nothing of what is computed."""

    n_out: int = 0
    mixer: Layer | None = None
    mlp_ratio: int = 4
    activation: object = "gelu"
    norm: str = "layer"
    norm_eps: float | None = None
    norm_zero_centered: bool = False
    sandwich: bool = False
    bias: bool = True
    weight_init: object = "xavier"
    ffn: str = "mlp"
    ffn_width: int | None = None
    n_experts: int = 0
    top_k: int = 1
    experts_held: tuple = ()
    routed_scale: float = 1.0
    router: str = "sigmoid"
    expert_gated: bool = True
    shared_expert_width: int = 0
    shared_expert_gate: bool = True
    recompute_moe: bool = False

    input_family = _inputs.RecurrentType

    def _held(self):
        """(first, end) of the experts this layer holds."""
        first, end = self.experts_held or (0, self.n_experts)
        if not 0 <= first < end <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} does not "
                             f"lie in 0..{self.n_experts}")
        return int(first), int(end)

    def _norm(self):
        if self.norm not in ("layer", "rms"):
            raise ValueError(f"norm is 'layer' or 'rms', got {self.norm!r}")
        kw = {} if self.norm_eps is None else {"eps": self.norm_eps}
        if self.norm == "layer":
            if self.norm_zero_centered:
                raise ValueError("norm_zero_centered is the RMS norm's")
            return LayerNormalization(**kw)
        return RMSNorm(zero_centered=self.norm_zero_centered, **kw)

    def output_type(self, input_type):
        return _inputs.RecurrentType(self.n_out, input_type.timesteps)

    def init(self, key, input_type, dtype=jnp.float32):
        assert input_type.size == self.n_out, \
            "TransformerBlock requires input size == n_out (residual)"
        if self.ffn not in ("mlp", "gated", "moe", "none"):
            raise ValueError("ffn is 'mlp', 'gated', 'moe' or 'none', got "
                             f"{self.ffn!r}")
        mixer = self.mixer
        if isinstance(mixer, str):
            raise ValueError(
                f"mixer is a layer object or None, got the string "
                f"{mixer!r}: a configuration saved before the block held "
                "its mixer (MIGRATION.md, 'TransformerBlock.mixer')")
        if mixer is None and self.ffn == "none":
            raise ValueError("a block has a mixer, an FFN or both")
        if self.sandwich and (mixer is None or self.ffn == "none"):
            raise ValueError("the sandwich norms belong to a whole block")
        if self.bias and self.ffn not in ("mlp", "none"):
            raise ValueError(f"the {self.ffn} FFN has no biases: set "
                             "bias=False")
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError("router is 'sigmoid' or 'softmax', got "
                             f"{self.router!r}")
        if self.shared_expert_width and self.ffn != "moe":
            raise ValueError("the shared expert belongs to ffn='moe'")
        norm = self._norm()
        k1, k2, k3, k4 = jax.random.split(key, 4)
        hidden = self.ffn_width or self.n_out * self.mlp_ratio
        it = _inputs.RecurrentType(self.n_out, input_type.timesteps)
        width = self.n_out if mixer is None else mixer.output_type(it).size
        if width != self.n_out:
            raise ValueError(
                f"the mixer {type(mixer).__name__} puts out {width} "
                f"features, the block's residual is {self.n_out} wide")

        def weight(k, n_in, n_out):
            return _init.init_weight(self.weight_init, k, (n_in, n_out),
                                     n_in, n_out, dtype)

        p = {}
        if mixer is not None:
            p.update({"ln1": norm.init(k1, it, dtype),
                      mixer.param_key: mixer.init(k1, it, dtype)})
        if self.ffn != "none":
            p["ln2"] = norm.init(k2, it, dtype)
        if self.sandwich:
            p["ln1_post"] = norm.init(k1, it, dtype)
            p["ln2_post"] = norm.init(k2, it, dtype)
        if self.ffn == "gated":
            k3g, k3u = jax.random.split(k3)
            p["mlp_Wg"] = weight(k3g, self.n_out, hidden)
            p["mlp_Wu"] = weight(k3u, self.n_out, hidden)
            p["mlp_Wd"] = weight(k4, hidden, self.n_out)
        elif self.ffn == "moe":
            first, end = self._held()
            kr, kg, ku = jax.random.split(k3, 3)

            def experts(k, n_in, n_out):
                return jnp.stack([weight(kk, n_in, n_out) for kk in
                                  jax.random.split(k, end - first)])

            p["moe_router"] = weight(kr, self.n_out, self.n_experts)
            if self.expert_gated:
                p["moe_Wg"] = experts(kg, self.n_out, hidden)
            p["moe_Wu"] = experts(ku, self.n_out, hidden)
            p["moe_Wd"] = experts(k4, hidden, self.n_out)
            if self.shared_expert_width:
                ks = jax.random.split(jax.random.fold_in(k3, 1), 4)
                fs = self.shared_expert_width
                if self.expert_gated:
                    p["moe_shared_Wg"] = weight(ks[0], self.n_out, fs)
                p["moe_shared_Wu"] = weight(ks[1], self.n_out, fs)
                p["moe_shared_Wd"] = weight(ks[2], fs, self.n_out)
                if self.shared_expert_gate:
                    p["moe_shared_gate"] = weight(ks[3], self.n_out, 1)
        elif self.ffn == "mlp":
            p["mlp_W1"] = weight(k3, self.n_out, hidden)
            p["mlp_W2"] = weight(k4, hidden, self.n_out)
        if self.bias and self.ffn == "mlp":
            p["mlp_b1"] = jnp.zeros((hidden,), dtype)
            p["mlp_b2"] = jnp.zeros((self.n_out,), dtype)
        return p

    def init_state(self, input_type, dtype=jnp.float32):
        if self.ffn != "moe":
            return {}
        first, end = self._held()
        counts = {"moe_load": jnp.zeros((end - first,), dtype),
                  "moe_elsewhere": jnp.zeros((1,), dtype)}
        if self.router == "softmax":     # no bias moves its selection
            return counts
        return {"expert_bias": jnp.zeros((self.n_experts,), dtype), **counts}

    def _moe(self, params, state, h):
        """The routed experts' part of the result, with the shared
        expert's where the block has one, and the state with this step's
        row counts."""
        from deeplearning4j_tpu.nn import activations as _act
        from deeplearning4j_tpu.nn.layers import moe as _moe
        if "moe_load" not in state:
            raise ValueError(
                "ffn='moe' keeps its load (and the sigmoid router its "
                "expert bias) in the layer's state; this caller hands the "
                "block none")
        act = _act.get(self.activation)
        routed = functools.partial(
            _moe.routed_experts, top_k=self.top_k, held=self._held(),
            scale=self.routed_scale, act=act, score=self.router)
        if self.recompute_moe:
            routed = jax.checkpoint(routed)
        with jax.named_scope("moe"):
            y, load, elsewhere = routed(
                h, params["moe_router"], params.get("moe_Wg"),
                params["moe_Wu"], params["moe_Wd"],
                state.get("expert_bias"))
            if self.shared_expert_width:
                with jax.named_scope("moe_shared"):
                    if self.expert_gated:
                        m = (act(matmul(h, params["moe_shared_Wg"]))
                             * matmul(h, params["moe_shared_Wu"]))
                    else:
                        m = act(matmul(h, params["moe_shared_Wu"]))
                    if self.shared_expert_gate:
                        gate = jax.nn.sigmoid(
                            matmul(h, params["moe_shared_gate"]))
                        y = y + gate * matmul(m, params["moe_shared_Wd"])
                    else:
                        y = y + matmul(m, params["moe_shared_Wd"])
        dt = state["moe_load"].dtype
        return y, {**state, "moe_load": load.astype(dt),
                   "moe_elsewhere": elsewhere.astype(dt)}

    def _ffn(self, params, h):
        from deeplearning4j_tpu.nn import activations as _act
        act = _act.get(self.activation)
        if self.ffn == "gated":
            m = act(matmul(h, params["mlp_Wg"])) * matmul(h, params["mlp_Wu"])
            return matmul(m, params["mlp_Wd"])
        m = matmul(h, params["mlp_W1"])
        m = act(m + params["mlp_b1"] if self.bias else m)
        m = matmul(m, params["mlp_W2"])
        return m + params["mlp_b2"] if self.bias else m

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        norm, mixer = self._norm(), self.mixer
        if mixer is not None:
            with jax.named_scope("attn"):
                h, _ = norm.apply(params["ln1"], {}, x)
                attn, _ = mixer.apply(params[mixer.param_key], {}, h,
                                      train=train, mask=mask)
                if self.sandwich:
                    attn, _ = norm.apply(params["ln1_post"], {}, attn)
                x = x + attn
        if self.ffn == "none":
            return x, state
        with jax.named_scope("mlp"):
            h, _ = norm.apply(params["ln2"], {}, x)
            b, t, f = h.shape
            if self.ffn == "moe":
                m, state = self._moe(params, state, h.reshape(b * t, f))
            else:
                m = self._ffn(params, h.reshape(b * t, f))
            if self.sandwich:
                m, _ = norm.apply(params["ln2_post"], {}, m)
            return x + m.reshape(b, t, f), state

    def regularization_penalty(self, params):
        return 0.0
