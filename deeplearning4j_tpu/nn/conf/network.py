"""Network configuration DSL.

Reference analog: NeuralNetConfiguration.Builder -> MultiLayerConfiguration
(/root/reference/deeplearning4j-nn/.../nn/conf/NeuralNetConfiguration.java:569
Builder, :724 list(); MultiLayerConfiguration.java toJson:120/fromJson:138).

The TPU-native shape: configs are frozen dataclasses; ``NeuralNetConfig`` is
the builder carrying global defaults (activation, weight init, updater, l1/l2,
dropout, seed) that cascade into per-layer configs exactly like the
reference's Builder.list(...) flow — a layer field left at its class default
is overridden by the global default. JSON round-trip via the serde registry.
"""

from __future__ import annotations

import dataclasses

from deeplearning4j_tpu.nn import updaters as _updaters
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.utils import serde

# fields that cascade from global defaults into layers when left unset
_CASCADE_FIELDS = ("activation", "weight_init", "bias_init", "l1", "l2",
                   "l1_bias", "l2_bias", "dropout", "constraints")


@serde.register_config
@dataclasses.dataclass(frozen=True)
class ParamTie:
    """Layer ``layer`` is given, under the name ``name``, layer
    ``source_layer``'s parameter ``source_name``: one leaf in the
    parameter tree, at its owner, read by both layers (a language model's
    embedding table read again by its output layer). The gradient of the
    leaf is the sum over its uses; the updater, ``num_params()`` and a
    checkpoint see it once. The reading layer's ``init`` does not make
    the parameter; ``MultiLayerNetwork`` hands it over in every forward
    pass."""

    layer: int = 0
    name: str = ""
    source_layer: int = 0
    source_name: str = ""


@serde.register_config
@dataclasses.dataclass(frozen=True)
class MultiLayerConfiguration:
    """Immutable, JSON-round-trippable sequential-network config."""

    layers: tuple = ()
    input_type: InputType | None = None
    updater: object = dataclasses.field(default_factory=_updaters.Sgd)
    gradient_normalization: str = "none"
    gradient_normalization_threshold: float = 1.0
    backprop_type: str = "standard"  # standard | tbptt
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    seed: int = 12345
    mini_batch: bool = True  # reference: miniBatch flag (score averaging)
    # remat each layer's forward during backprop: HBM for FLOPs (SURVEY §0
    # "jax.checkpoint / rematerialisation" bullet; no reference analog —
    # workspaces solved a different memory problem)
    gradient_checkpointing: bool = False
    # parameters one layer reads from another (ParamTie), each a single
    # leaf at its owner
    ties: tuple = ()

    def to_json(self, indent=2):
        return serde.to_json(self, indent=indent)

    @staticmethod
    def from_json(s):
        conf = serde.from_json(s)
        assert isinstance(conf, MultiLayerConfiguration)
        return conf

    def layer_input_types(self):
        """Shape inference along the stack (reference: preprocessor insertion logic
        in MultiLayerConfiguration.Builder — here conversions are implicit,
        see nn/conf/inputs.py adapt())."""
        from deeplearning4j_tpu.nn.conf import inputs as _inputs
        types = []
        cur = self.input_type
        if cur is None:
            raise ValueError("MultiLayerConfiguration requires input_type for shape inference")
        for layer in self.layers:
            fam = layer.input_family
            if fam is not None and not isinstance(cur, fam):
                cur = _inputs.adapted_type(cur, fam)
            types.append(cur)
            cur = layer.output_type(cur)
        return types, cur


@dataclasses.dataclass
class NeuralNetConfig:
    """Builder with cascading global defaults (reference:
    NeuralNetConfiguration.Builder, default updater Sgd at :580)."""

    seed: int = 12345
    activation: object = None
    weight_init: object = None
    bias_init: float = None
    l1: float = None
    l2: float = None
    dropout: float = None
    updater: object = dataclasses.field(default_factory=_updaters.Sgd)
    gradient_normalization: str = "none"
    gradient_normalization_threshold: float = 1.0

    def list(self, *layers, input_type=None, backprop_type="standard",
             tbptt_fwd_length=20, tbptt_back_length=20,
             gradient_checkpointing=False, ties=()) -> MultiLayerConfiguration:
        cascaded = tuple(self._cascade(l) for l in layers)
        return MultiLayerConfiguration(
            layers=cascaded, input_type=input_type,
            updater=self.updater if not isinstance(self.updater, str) else _updaters.get(self.updater),
            gradient_normalization=self.gradient_normalization,
            gradient_normalization_threshold=self.gradient_normalization_threshold,
            backprop_type=backprop_type, tbptt_fwd_length=tbptt_fwd_length,
            tbptt_back_length=tbptt_back_length, seed=self.seed,
            gradient_checkpointing=gradient_checkpointing,
            ties=tuple(ties),
        )

    def _cascade(self, layer):
        updates = {}
        fields = {f.name: f for f in dataclasses.fields(layer)}
        for name in _CASCADE_FIELDS:
            global_val = getattr(self, name, None)
            if global_val is None or name not in fields:
                continue
            f = fields[name]
            default = f.default if f.default is not dataclasses.MISSING else None
            if getattr(layer, name) == default:
                updates[name] = global_val
        return dataclasses.replace(layer, **updates) if updates else layer
