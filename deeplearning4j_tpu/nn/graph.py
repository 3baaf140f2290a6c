"""ComputationGraph: arbitrary-DAG networks.

Reference analog: nn/graph/ComputationGraph.java (3422 LoC;
topologicalSortOrder:1194, feedForward:1384, computeGradientAndScore:1302) +
ComputationGraphConfiguration.java + vertex impls nn/graph/vertex/impl/
(ElementWise, Merge, Subset, Stack/Unstack, Scale, Shift, L2Normalize, L2,
Reshape, PoolHelper, Preprocessor, Layer, Input) and RNN vertices
nn/conf/graph/rnn/ (LastTimeStepVertex, DuplicateToTimeSeriesVertex), all in
/root/reference/deeplearning4j-nn.

TPU-native: the DAG is topologically sorted once at build; the whole forward
(+backward in the train step) is a single jitted XLA computation — vertices
are pure functions over pytrees, so XLA fuses across vertex boundaries (the
reference executes vertex-by-vertex through JNI).

Multi-input/multi-output supported: ``fit({'in': x}, {'out': y})``; loss =
sum of output-layer losses (matching the reference's multi-output score).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import telemetry as _tm
from deeplearning4j_tpu.telemetry import flight as _flight
from deeplearning4j_tpu.telemetry import health as _health
from deeplearning4j_tpu.nn import gradnorm as _gradnorm
from deeplearning4j_tpu.nn import listeners as _listeners
from deeplearning4j_tpu.nn import losses as _losses
from deeplearning4j_tpu.nn import scopes as _scopes
from deeplearning4j_tpu.nn import updaters as _updaters
from deeplearning4j_tpu.nn.conf import inputs as _inputs
from deeplearning4j_tpu.nn.layers import base as _base_layers
from deeplearning4j_tpu.utils import dtypes as _dtypes
from deeplearning4j_tpu.utils import serde


def _loss_mask_for(mask, label):
    """The batch mask as an output's label mask ONLY when its layout
    matches that output's per-example loss: [B] pairs with pooled
    (<=2-d) labels, [B, T] with time-distributed (>=3-d) labels. A
    mixed-layout graph (one temporal feature mask, pooled heads) keeps
    the head unmasked rather than mis-broadcasting — pass explicit
    ``label_masks`` to override."""
    if mask is None:
        return None
    if mask.ndim == 1 and label.ndim <= 2:
        return mask
    if mask.ndim == 2 and label.ndim >= 3:
        return mask
    return None


# --------------------------------------------------------------------------
# Graph vertices
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GraphVertex:
    """Base: pure function over a list of input activations."""

    def output_type(self, input_types):
        assert len(input_types) == 1
        return input_types[0]

    def init(self, key, input_types, dtype=jnp.float32):
        return {}

    def init_state(self, input_types, dtype=jnp.float32):
        return {}

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        return xs[0], state

    def regularization_penalty(self, params):
        return 0.0


@serde.register_config
@dataclasses.dataclass(frozen=True)
class LayerVertex(GraphVertex):
    """Wraps any layer from the catalog (reference: vertex/impl/LayerVertex.java)."""

    layer: object = None

    def _adapted(self, input_types):
        it = input_types[0]
        fam = self.layer.input_family
        if fam is not None and not isinstance(it, fam):
            return _inputs.adapted_type(it, fam)
        return it

    def output_type(self, input_types):
        return self.layer.output_type(self._adapted(input_types))

    def init(self, key, input_types, dtype=jnp.float32):
        return self.layer.init(key, self._adapted(input_types), dtype)

    def init_state(self, input_types, dtype=jnp.float32):
        return self.layer.init_state(self._adapted(input_types), dtype)

    def _adapt(self, x):
        # family adaptation by rank (jit-safe: static shapes)
        if self.layer.input_family is _inputs.FeedForwardType and x.ndim > 2:
            x = x.reshape((x.shape[0], -1))
        return x

    def pre_output(self, params, xs):
        return self.layer.pre_output(params, self._adapt(xs[0]))

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        x = self._adapt(xs[0])
        kwargs = {}
        # 1-d masks are example-validity (shape bucketing), not [B, T]
        # timestep masks — mask-aware layers only get the latter
        if mask is not None and mask.ndim >= 2 \
                and "mask" in inspect.signature(type(self.layer).apply).parameters:
            kwargs["mask"] = mask
        return self.layer.apply(params, state, x, train=train, rng=rng, **kwargs)

    # recurrent-carry plumbing (TBPTT / rnnTimeStep): delegate to the
    # wrapped layer when it is recurrent
    def has_carry(self):
        return hasattr(self.layer, "apply_with_carry")

    def zero_carry(self, batch, dtype=jnp.float32):
        return self.layer.zero_carry(batch, dtype)

    def apply_with_carry(self, params, carry, xs, *, mask=None):
        return self.layer.apply_with_carry(params, carry, xs[0], mask=mask)

    def regularization_penalty(self, params):
        return self.layer.regularization_penalty(params) if params else 0.0


@serde.register_config
@dataclasses.dataclass(frozen=True)
class MergeVertex(GraphVertex):
    """Concatenate along the feature/channel axis (reference: MergeVertex.java)."""

    def output_type(self, input_types):
        t0 = input_types[0]
        if isinstance(t0, _inputs.ConvolutionalType):
            return _inputs.ConvolutionalType(t0.height, t0.width,
                                             sum(t.channels for t in input_types))
        if isinstance(t0, _inputs.RecurrentType):
            return _inputs.RecurrentType(sum(t.size for t in input_types), t0.timesteps)
        return _inputs.FeedForwardType(sum(t.size for t in input_types))

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        return jnp.concatenate(xs, axis=-1), state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class ElementWiseVertex(GraphVertex):
    """add | subtract | product | average | max (reference: ElementWiseVertex.java)."""

    op: str = "add"

    def output_type(self, input_types):
        return input_types[0]

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        if self.op == "add":
            return functools.reduce(jnp.add, xs), state
        if self.op == "subtract":
            assert len(xs) == 2
            return xs[0] - xs[1], state
        if self.op == "product":
            return functools.reduce(jnp.multiply, xs), state
        if self.op == "average":
            return functools.reduce(jnp.add, xs) / len(xs), state
        if self.op == "max":
            return functools.reduce(jnp.maximum, xs), state
        raise ValueError(f"Unknown elementwise op {self.op!r}")


@serde.register_config
@dataclasses.dataclass(frozen=True)
class SubsetVertex(GraphVertex):
    """Feature-range slice [from, to] inclusive (reference: SubsetVertex.java)."""

    from_idx: int = 0
    to_idx: int = 0

    def output_type(self, input_types):
        n = self.to_idx - self.from_idx + 1
        t = input_types[0]
        if isinstance(t, _inputs.RecurrentType):
            return _inputs.RecurrentType(n, t.timesteps)
        if isinstance(t, _inputs.ConvolutionalType):
            return _inputs.ConvolutionalType(t.height, t.width, n)
        return _inputs.FeedForwardType(n)

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        return xs[0][..., self.from_idx:self.to_idx + 1], state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class StackVertex(GraphVertex):
    """Stack along batch dim (reference: StackVertex.java)."""

    def output_type(self, input_types):
        return input_types[0]  # batch dim is not part of InputType

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        return jnp.concatenate(xs, axis=0), state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class UnstackVertex(GraphVertex):
    """Take slice ``index`` of ``stack_size`` along batch (reference: UnstackVertex.java)."""

    index: int = 0
    stack_size: int = 1

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        x = xs[0]
        step = x.shape[0] // self.stack_size
        return x[self.index * step:(self.index + 1) * step], state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class ScaleVertex(GraphVertex):
    factor: float = 1.0

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        return xs[0] * self.factor, state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class ShiftVertex(GraphVertex):
    amount: float = 0.0

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        return xs[0] + self.amount, state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class L2NormalizeVertex(GraphVertex):
    eps: float = 1e-8

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        x = xs[0]
        norm = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim)), keepdims=True))
        return x / (norm + self.eps), state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class L2Vertex(GraphVertex):
    """Pairwise L2 distance between two inputs -> [batch, 1] (reference: L2Vertex.java)."""

    eps: float = 1e-8

    def output_type(self, input_types):
        return _inputs.FeedForwardType(1)

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        a, b = xs
        d = (a - b).reshape((a.shape[0], -1))
        return jnp.sqrt(jnp.sum(d * d, axis=1, keepdims=True) + self.eps), state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class ReshapeVertex(GraphVertex):
    """Reshape trailing dims, batch preserved (reference: ReshapeVertex.java)."""

    shape: tuple = ()
    output_input_type: object = None

    def output_type(self, input_types):
        return self.output_input_type or input_types[0]

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        return xs[0].reshape((xs[0].shape[0],) + tuple(self.shape)), state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class LastTimeStepVertex(GraphVertex):
    """[B,T,F] -> [B,F] mask-aware (reference: rnn/LastTimeStepVertex.java)."""

    def output_type(self, input_types):
        return _inputs.FeedForwardType(input_types[0].size)

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        x = xs[0]
        if mask is None:
            return x[:, -1, :], state
        idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
        return x[jnp.arange(x.shape[0]), idx, :], state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class DuplicateToTimeSeriesVertex(GraphVertex):
    """[B,F] -> [B,T,F] broadcast over time (reference:
    rnn/DuplicateToTimeSeriesVertex.java). T taken from a reference input."""

    timesteps: int = 1

    def output_type(self, input_types):
        return _inputs.RecurrentType(input_types[0].size, self.timesteps)

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        return jnp.broadcast_to(xs[0][:, None, :],
                                (xs[0].shape[0], self.timesteps, xs[0].shape[-1])), state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class PoolHelperVertex(GraphVertex):
    """Strip first row/col (reference: PoolHelperVertex.java — GoogLeNet
    import compatibility)."""

    def output_type(self, input_types):
        t = input_types[0]
        return _inputs.ConvolutionalType(t.height - 1, t.width - 1, t.channels)

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        return xs[0][:, 1:, 1:, :], state


@serde.register_config
@dataclasses.dataclass(frozen=True)
class PreprocessorVertex(GraphVertex):
    """Explicit family conversion (reference: PreprocessorVertex.java).
    kind: cnn_to_ff | ff_to_cnn | rnn_to_ff | ff_to_rnn | cnn_to_rnn"""

    kind: str = "cnn_to_ff"
    height: int = 0
    width: int = 0
    channels: int = 0
    timesteps: int = 0

    def output_type(self, input_types):
        t = input_types[0]
        if self.kind == "cnn_to_ff":
            return _inputs.FeedForwardType(t.flat_size)
        if self.kind == "ff_to_cnn":
            return _inputs.ConvolutionalType(self.height, self.width, self.channels)
        if self.kind == "rnn_to_ff":
            return _inputs.FeedForwardType(t.size)
        if self.kind == "ff_to_rnn":
            return _inputs.RecurrentType(t.size, self.timesteps)
        if self.kind == "cnn_to_rnn":
            return _inputs.RecurrentType(t.width * t.channels, t.height)
        raise ValueError(self.kind)

    def apply(self, params, state, xs, *, train=False, rng=None, mask=None):
        x = xs[0]
        if self.kind == "cnn_to_ff":
            return x.reshape((x.shape[0], -1)), state
        if self.kind == "ff_to_cnn":
            return x.reshape((x.shape[0], self.height, self.width, self.channels)), state
        if self.kind == "rnn_to_ff":
            return x.reshape((-1, x.shape[-1])), state
        if self.kind == "ff_to_rnn":
            return x.reshape((-1, self.timesteps, x.shape[-1])), state
        if self.kind == "cnn_to_rnn":
            return x.reshape((x.shape[0], x.shape[1], -1)), state
        raise ValueError(self.kind)


# --------------------------------------------------------------------------
# Graph configuration
# --------------------------------------------------------------------------


@serde.register_config
@dataclasses.dataclass(frozen=True)
class VertexDef:
    name: str = ""
    vertex: object = None
    inputs: tuple = ()


@serde.register_config
@dataclasses.dataclass(frozen=True)
class GraphConfiguration:
    """(reference: ComputationGraphConfiguration + its GraphBuilder)."""

    inputs: tuple = ()          # input names
    input_types: tuple = ()     # matching InputTypes
    vertices: tuple = ()        # VertexDef tuple (definition order)
    outputs: tuple = ()         # names of output vertices
    updater: object = dataclasses.field(default_factory=_updaters.Sgd)
    gradient_normalization: str = "none"
    gradient_normalization_threshold: float = 1.0
    seed: int = 12345
    # remat each vertex's forward during backprop: HBM for FLOPs
    gradient_checkpointing: bool = False
    # truncated BPTT (reference: ComputationGraph.doTruncatedBPTT:2595 +
    # the fit branches at :937/:1038/:1162)
    backprop_type: str = "standard"  # standard | tbptt
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    # coarser remat: group vertices sharing a name prefix (up to the first
    # '_') into ONE jax.checkpoint region on the training path, so only
    # block BOUNDARY activations are stashed for backward and everything
    # inside a block (conv outputs, BN pre-activations) is recomputed.
    # For an HBM-bound model (PROFILE.md: ResNet50 at v5e bandwidth peak)
    # this trades idle-MXU FLOPs for the activation-stash traffic that
    # bounds the step. "prefix" is the only mode; None disables.
    checkpoint_scope: str | None = None

    def to_json(self, indent=2):
        return serde.to_json(self, indent=indent)

    @staticmethod
    def from_json(s):
        conf = serde.from_json(s)
        assert isinstance(conf, GraphConfiguration)
        return conf

    def topological_order(self):
        """Kahn topo sort (reference: topologicalSortOrder:1194)."""
        defs = {v.name: v for v in self.vertices}
        indeg = {v.name: 0 for v in self.vertices}
        dependents = {name: [] for name in list(defs) + list(self.inputs)}
        for v in self.vertices:
            for inp in v.inputs:
                if inp not in defs and inp not in self.inputs:
                    raise ValueError(f"Vertex {v.name!r} input {inp!r} undefined")
                if inp in defs:
                    indeg[v.name] += 1
                dependents[inp].append(v.name)
        order = [n for n, d in sorted(indeg.items()) if d == 0]
        queue = list(order)
        seen = set(order)
        result = []
        while queue:
            n = queue.pop(0)
            result.append(n)
            for dep in dependents[n]:
                indeg[dep] -= 1
                if indeg[dep] == 0 and dep not in seen:
                    seen.add(dep)
                    queue.append(dep)
        if len(result) != len(self.vertices):
            raise ValueError("Graph has a cycle")
        return result

    def vertex_types(self):
        """Shape inference over the DAG. Returns {name: output InputType}."""
        defs = {v.name: v for v in self.vertices}
        types = dict(zip(self.inputs, self.input_types))
        for name in self.topological_order():
            v = defs[name]
            in_types = [types[i] for i in v.inputs]
            types[name] = v.vertex.output_type(in_types)
        return types


class GraphBuilder:
    """Fluent builder (reference: ComputationGraphConfiguration.GraphBuilder)."""

    def __init__(self, updater=None, seed=12345, gradient_normalization="none",
                 gradient_normalization_threshold=1.0,
                 gradient_checkpointing=False, checkpoint_scope=None,
                 backprop_type="standard", tbptt_fwd_length=20,
                 tbptt_back_length=20):
        self._inputs = []
        self._input_types = []
        self._vertices = []
        self._outputs = []
        self._updater = updater or _updaters.Sgd()
        self._seed = seed
        self._gn = gradient_normalization
        self._gnt = gradient_normalization_threshold
        self._remat = gradient_checkpointing
        self._ckpt_scope = checkpoint_scope
        self._backprop_type = backprop_type
        self._tbptt_fwd = tbptt_fwd_length
        self._tbptt_back = tbptt_back_length

    def add_inputs(self, *names):
        self._inputs.extend(names)
        return self

    def set_input_types(self, *types):
        self._input_types.extend(types)
        return self

    def add_layer(self, name, layer, *inputs):
        self._vertices.append(VertexDef(name, LayerVertex(layer=layer), tuple(inputs)))
        return self

    def add_vertex(self, name, vertex, *inputs):
        self._vertices.append(VertexDef(name, vertex, tuple(inputs)))
        return self

    def set_outputs(self, *names):
        self._outputs.extend(names)
        return self

    def add_module(self, module, layer_name, input_size, config, input_layer):
        """Append a reusable graph fragment via the GraphBuilderModule SPI
        (reference: GraphBuilderModule.updateBuilder)."""
        return module.update_builder(self, layer_name, input_size, config,
                                     input_layer)

    def last_vertex_name(self):
        """Name of the most recently added vertex (modules add their output
        vertex last, so chains continue from here)."""
        return self._vertices[-1].name if self._vertices else None

    def build(self) -> GraphConfiguration:
        conf = GraphConfiguration(
            inputs=tuple(self._inputs), input_types=tuple(self._input_types),
            vertices=tuple(self._vertices), outputs=tuple(self._outputs),
            updater=self._updater, seed=self._seed,
            gradient_normalization=self._gn,
            gradient_normalization_threshold=self._gnt,
            gradient_checkpointing=self._remat,
            checkpoint_scope=self._ckpt_scope,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back)
        conf.topological_order()  # validate
        return conf


# --------------------------------------------------------------------------
# ComputationGraph
# --------------------------------------------------------------------------


class ComputationGraph:
    def __init__(self, conf: GraphConfiguration):
        self.conf = conf
        self._defs = {v.name: v for v in conf.vertices}
        self._order = conf.topological_order()
        self._types = conf.vertex_types()
        self._segments = (self._build_segments()
                          if conf.checkpoint_scope == "prefix" else None)
        self.params = None
        self.state = None
        self.opt_state = None
        self.iteration = 0
        self.epoch = 0
        self.listeners = []
        self.score_value = None
        self._train_step = None
        self._train_step_health = None
        self._rng = jax.random.PRNGKey(conf.seed)

    def init(self, rng=None, dtype=None):
        rng = self._rng if rng is None else rng
        dtype = dtype or _dtypes.get_policy().param_dtype
        params, state = {}, {}
        with _tm.span("net.init"):
            for name in self._order:
                v = self._defs[name]
                in_types = [self._types[i] for i in v.inputs]
                rng, sub = jax.random.split(rng)
                params[name] = v.vertex.init(sub, in_types, dtype)
                state[name] = v.vertex.init_state(in_types, dtype)
            self.params, self.state = params, state
            self.opt_state = self.conf.updater.init(params)
        return params, state

    def _build_segments(self):
        """Partition the topo order into checkpoint segments for the
        ``checkpoint_scope="prefix"`` mode: a maximal contiguous run of >= 2
        vertices sharing the name prefix before the first '_' becomes one
        ("group", names, external_inputs, boundary_outputs) region; loss /
        network-output vertices always stay singles. Only activations at
        group boundaries are stashed for backward — the bottleneck-block
        granularity ResNet-style graphs need (per-vertex jax.checkpoint
        stores every vertex input and saves nothing)."""
        dependents = {}
        for v in self.conf.vertices:
            for inp in v.inputs:
                dependents.setdefault(inp, set()).add(v.name)

        def scope_of(name):
            if name in self.conf.outputs:
                return None
            v = self._defs[name]
            layer = v.vertex.layer if isinstance(v.vertex, LayerVertex) \
                else None
            if layer is not None and hasattr(layer, "loss_from_features"):
                return None
            return name.split("_", 1)[0] if "_" in name else None

        segments = []
        i = 0
        order = self._order
        while i < len(order):
            sc = scope_of(order[i])
            j = i + 1
            while sc is not None and j < len(order) \
                    and scope_of(order[j]) == sc:
                j += 1
            if sc is None or j - i < 2:
                segments.append(("single", order[i]))
                i += 1
                continue
            names = order[i:j]
            produced = set(names)
            ext = []
            for n in names:
                for inp in self._defs[n].inputs:
                    if inp not in produced and inp not in ext:
                        ext.append(inp)
            after = set(order[j:])
            bnd = [n for n in names
                   if n in self.conf.outputs
                   or dependents.get(n, set()) & after]
            segments.append(("group", tuple(names), tuple(ext), tuple(bnd)))
            i = j
        return segments

    def _run_group(self, seg, params, state, acts, new_state, subs, mask,
                   train):
        """Execute one checkpoint group: recompute-in-backward region over
        its member vertices. Only boundary outputs land in ``acts``."""
        _, names, ext, bnd = seg

        frozen = getattr(self, "frozen_vertices", set())

        def run(gp, gs, ext_vals, subs_, m):
            local = dict(zip(ext, ext_vals))
            ns = {}
            for k, n in enumerate(names):
                v = self._defs[n]
                xs = [local[i] for i in v.inputs]
                with _scopes.vertex(n, v.vertex):
                    local[n], ns[n] = v.vertex.apply(
                        gp[n], gs[n], xs, train=train and n not in frozen,
                        rng=subs_[k], mask=m)
            return [local[n] for n in bnd], ns

        run = jax.checkpoint(run)
        outs, ns = run({n: params[n] for n in names},
                       {n: state[n] for n in names},
                       [acts[i] for i in ext], subs, mask)
        for n, val in zip(bnd, outs):
            acts[n] = val
        new_state.update(ns)

    def _forward_pass(self, params, state, inputs, *, train=False, rng=None,
                      mask=None, labels=None, label_masks=None,
                      carries=None):
        """THE single topological traversal all forward entry points share.
        Returns (acts, new_state, loss[, new_carries]); ``loss`` is None
        unless ``labels`` is given, in which case output-vertex losses
        accumulate (feature-loss heads like CenterLossOutputLayer receive
        their input activations). ``carries``: optional {vertex: carry}
        dict threading recurrent hidden state (TBPTT / rnnTimeStep —
        reference: doTruncatedBPTT:2595, rnnTimeStep on ComputationGraph);
        when given, recurrent LayerVertices run apply_with_carry and the
        updated carries are returned as a fourth element."""
        if not isinstance(inputs, dict):
            inputs = {self.conf.inputs[0]: jnp.asarray(inputs)}
        acts = dict(inputs)
        new_state = dict(state)
        new_carries = dict(carries) if carries is not None else None
        loss = 0.0 if labels is not None else None
        # scope-level remat applies on the loss/training path only —
        # feed_forward()'s contract (an activation for EVERY vertex) needs
        # the ungrouped traversal, and there is no backward there anyway;
        # carry-threaded passes also walk ungrouped
        use_groups = (self._segments is not None and labels is not None
                      and carries is None)
        walk = (self._segments if use_groups
                else [("single", n) for n in self._order])
        frozen = getattr(self, "frozen_vertices", set())
        for seg in walk:
            if seg[0] == "group":
                subs = []
                for _ in seg[1]:
                    if rng is not None:
                        rng, sub = jax.random.split(rng)
                        subs.append(sub)
                    else:
                        subs.append(None)
                self._run_group(seg, params, state, acts, new_state,
                                tuple(subs), mask, train)
                continue
            name = seg[1]
            v = self._defs[name]
            xs = [acts[i] for i in v.inputs]
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            layer = v.vertex.layer if isinstance(v.vertex, LayerVertex) else None
            if (labels is not None and name in self.conf.outputs
                    and layer is not None
                    and hasattr(layer, "loss_from_features")):
                x = xs[0]
                if (layer.input_family is _inputs.FeedForwardType
                        and x.ndim > 2):
                    x = x.reshape((x.shape[0], -1))
                # the MLN/reference convention: the batch mask doubles as
                # the label mask unless per-output label_masks are given
                # (MaskedReductionUtil zeroes padded steps from the score)
                lm = (label_masks or {}).get(name)
                if lm is None:
                    lm = _loss_mask_for(mask, labels[name])
                with _scopes.vertex(name, v.vertex), jax.named_scope("loss"):
                    l_i, preds, st = layer.loss_from_features(
                        params[name], state[name], x, labels[name], lm,
                        train=train and name not in frozen)
                loss = loss + l_i
                acts[name], new_state[name] = preds, st
            elif (new_carries is not None and isinstance(v.vertex,
                                                         LayerVertex)
                  and v.vertex.has_carry()):
                with _scopes.vertex(name, v.vertex):
                    acts[name], new_carries[name] = \
                        v.vertex.apply_with_carry(
                            params[name], new_carries.get(name), xs,
                            mask=mask)
            else:
                # FrozenLayer.java:23: frozen vertices forward in TEST mode
                # regardless of the network's mode (running-stat BN, no
                # stat updates, no dropout)
                l_train = train and name not in frozen
                is_output = labels is not None and name in self.conf.outputs
                # a softmax head under a cross-entropy: the loss from the
                # logits; its activations are for the caller, and dead
                # code in a train step
                logits_loss = _losses.from_logits(layer) if is_output \
                    else None

                def run(p, s, x_list, r, m, _v=v.vertex, _train=l_train,
                        _logits=logits_loss is not None):
                    if _logits:
                        return _v.pre_output(p, x_list), s
                    return _v.apply(p, s, x_list, train=_train, rng=r,
                                    mask=m)

                if self.conf.gradient_checkpointing:
                    run = jax.checkpoint(run)  # remat: HBM for FLOPs
                with _scopes.vertex(name, v.vertex):
                    acts[name], new_state[name] = run(
                        params[name], state[name], xs, sub, mask)
                if is_output:
                    l_layer = layer if layer is not None else v.vertex
                    if not hasattr(l_layer, "compute_loss"):
                        raise ValueError(f"Output vertex {name!r} has no loss")
                    lm = (label_masks or {}).get(name)
                    if lm is None:  # MLN convention, shape-guarded
                        lm = _loss_mask_for(mask, labels[name])
                    with jax.named_scope("loss"):
                        loss = loss + (logits_loss or l_layer.compute_loss)(
                            acts[name], labels[name], lm)
                    if logits_loss is not None:
                        acts[name] = layer.activation_fn()(acts[name])
        if carries is not None:
            return acts, new_state, loss, new_carries
        return acts, new_state, loss

    def apply_fn(self, params, state, inputs, *, train=False, rng=None, mask=None):
        """inputs: dict name->array (or single array if one input).
        Returns (dict of output activations, new_state)."""
        acts, new_state, _ = self._forward_pass(params, state, inputs,
                                                train=train, rng=rng, mask=mask)
        return {o: acts[o] for o in self.conf.outputs}, new_state

    def feed_forward(self, inputs, *, train=False, mask=None):
        """Activations of EVERY vertex, name->array (reference:
        ComputationGraph.feedForward:1384 returns the full activation map)."""
        acts, _, _ = self._forward_pass(self.params, self.state, inputs,
                                        train=train, mask=mask)
        return acts

    def loss_fn(self, params, state, inputs, labels, *, train=True, rng=None,
                mask=None, label_masks=None, carries=None):
        """Sum of output-layer losses + regularization (reference:
        computeGradientAndScore:1302). With ``carries`` (TBPTT chunks) the
        aux gains the updated carries: (new_state, outs, new_carries)."""
        if not isinstance(labels, dict):
            labels = {self.conf.outputs[0]: labels}
        fwd = self._forward_pass(
            params, state, inputs, train=train, rng=rng, mask=mask,
            labels=labels, label_masks=label_masks, carries=carries)
        acts, new_state, loss = fwd[:3]
        with jax.named_scope("loss"):
            for name in self._order:
                v = self._defs[name]
                if params[name]:
                    loss = loss + v.vertex.regularization_penalty(
                        params[name])
            loss, new_state = _base_layers.pop_aux_losses(loss, new_state)
        outs = {o: acts[o] for o in self.conf.outputs}
        if carries is not None:
            return loss, (new_state, outs, fwd[3])
        return loss, (new_state, outs)

    # ------------------------------------------------------------------
    # truncated BPTT + streaming inference (reference:
    # ComputationGraph.doTruncatedBPTT:2595, rnnTimeStep) — carries thread
    # through recurrent LayerVertices with stop_gradient at chunk edges
    # ------------------------------------------------------------------

    def _zero_carries(self, batch, dtype):
        from deeplearning4j_tpu.nn.layers.rnn import (
            Bidirectional, GravesBidirectionalLSTM)
        for v in self.conf.vertices:
            layer = getattr(v.vertex, "layer", None)
            if isinstance(layer, (Bidirectional, GravesBidirectionalLSTM)):
                # the backward direction needs the FULL future sequence —
                # the reference's rnnTimeStep throws for bidirectional
                # layers too; silent per-chunk state resets would produce
                # wrong numerics without an error
                raise ValueError(
                    f"vertex {v.name!r}: bidirectional layers do not "
                    "support TBPTT / rnn_time_step streaming")
        return {v.name: v.vertex.zero_carry(batch, dtype)
                for v in self.conf.vertices
                if isinstance(v.vertex, LayerVertex) and v.vertex.has_carry()}

    def make_tbptt_step(self, jit=True):
        def tbptt_step(params, state, opt_state, carries, inputs, labels,
                       step, rng, mask=None):
            carries = jax.tree_util.tree_map(jax.lax.stop_gradient, carries)
            (loss, (new_state, _, new_carries)), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True)(
                    params, state, inputs, labels, train=True, rng=rng,
                    mask=mask, carries=carries)
            new_params, new_opt = self.apply_update(
                params, opt_state, self._normalized(grads), step)
            return new_params, new_state, new_opt, new_carries, loss

        return jax.jit(_scopes.stamped(tbptt_step)) if jit else tbptt_step

    @staticmethod
    def _chunk_time(tree, t0, t1):
        """Slice [B, T, ...] arrays along time; static [B, F] entries (and
        2D labels of a LastTimeStep-style head) pass through whole — the
        MLN path's y.ndim == 3 guard, per-entry."""
        return {k: (jnp.asarray(v)[:, t0:t1]
                    if np.ndim(v) == 3 else jnp.asarray(v))
                for k, v in tree.items()}

    @staticmethod
    def _time_major(inputs):
        """The [B, T, ...] entry driving chunking (a multi-input graph may
        list a static [B, F] input first — scan, don't take the first)."""
        for v in inputs.values():
            if np.ndim(v) == 3:
                return v
        return None

    def _fit_tbptt(self, inputs, labels, mask):
        if getattr(self, "_tbptt_step", None) is None:
            self._tbptt_step = self.make_tbptt_step()
        first = self._time_major(inputs)
        T = first.shape[1]
        L = self.conf.tbptt_fwd_length
        carries = self._zero_carries(first.shape[0], jnp.asarray(first).dtype)
        total = 0.0
        n_chunks = 0
        chunk_scores = []  # (iteration, device loss) for listener replay
        for t0 in range(0, T, L):
            ci = self._chunk_time(inputs, t0, t0 + L)
            cl = self._chunk_time(labels, t0, t0 + L)
            cm = jnp.asarray(mask[:, t0:t0 + L]) if mask is not None else None
            self._rng, sub = jax.random.split(self._rng)
            (self.params, self.state, self.opt_state, carries, loss) = \
                self._tbptt_step(self.params, self.state, self.opt_state,
                                 carries, ci, cl, self.iteration, sub, cm)
            total = total + loss  # device accumulate: no per-chunk sync
            n_chunks += 1
            self.iteration += 1
            self.score_value = loss
            if self.listeners:
                chunk_scores.append((self.iteration, loss))
        if chunk_scores:
            # ONE batched fetch for every chunk's listener callback —
            # per-chunk float(loss) would sync each TBPTT chunk
            # (graftlint R1); the callbacks fire after the macro-batch,
            # matching the device-accumulated score below
            vals = jax.device_get([s for _, s in chunk_scores])
            for (it, _), v in zip(chunk_scores, vals):
                for lst in self.listeners:
                    lst.iteration_done(self, it, float(v))
        self.score_value = float(total) / max(n_chunks, 1)
        return self.score_value

    def rnn_clear_previous_state(self):
        """(reference: ComputationGraph.rnnClearPreviousState)"""
        self._rnn_stream_state = None

    def rnn_time_step(self, inputs):
        """One timestep [B, F] (or a short [B,T,F] chunk) of streaming
        inference, carrying recurrent state between calls (reference:
        ComputationGraph.rnnTimeStep)."""
        if self.params is None:
            self.init()
        if not isinstance(inputs, dict):
            inputs = {self.conf.inputs[0]: jnp.asarray(inputs)}
        inputs = {k: jnp.asarray(v) for k, v in inputs.items()}
        first = next(iter(inputs.values()))
        squeeze = first.ndim == 2
        if squeeze:
            inputs = {k: v[:, None, :] for k, v in inputs.items()}
            first = next(iter(inputs.values()))
        carries = getattr(self, "_rnn_stream_state", None)
        if carries is None:
            carries = self._zero_carries(first.shape[0], first.dtype)
        acts, _, _, carries = self._forward_pass(
            self.params, self.state, inputs, train=False, carries=carries)
        self._rnn_stream_state = carries
        # squeeze only time-major [B,T,F] outputs; a LastTimeStep-style
        # head already emits [B,C] and must pass through untouched
        outs = {o: (acts[o][:, 0] if squeeze and acts[o].ndim == 3
                    else acts[o])
                for o in self.conf.outputs}
        if len(outs) == 1:
            return next(iter(outs.values()))
        return outs

    def compute_gradients(self, params, state, inputs, labels, *, rng=None,
                          mask=None):
        """Loss + normalized gradients (MultiLayerNetwork.compute_gradients
        contract — the distributed masters insert their gradient exchange
        between this and apply_update)."""
        (loss, (new_state, _)), grads = jax.value_and_grad(
            self.loss_fn, has_aux=True)(params, state, inputs, labels,
                                        train=True, rng=rng, mask=mask)
        return loss, new_state, self._normalized(grads)

    def _normalized(self, grads):
        """Per-vertex gradient normalisation, the one definition the
        plain and the TBPTT step share."""
        conf = self.conf
        if conf.gradient_normalization in (None, "none"):
            return grads
        with jax.named_scope("grad_norm"):
            return {k: _gradnorm.normalize_layer_grads(
                conf.gradient_normalization, g,
                conf.gradient_normalization_threshold)
                if g else g for k, g in grads.items()}

    def apply_update(self, params, opt_state, grads, step):
        with jax.named_scope("updater"):
            updates, new_opt = self.conf.updater.update(grads, opt_state,
                                                        params, step)
            new_params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                                updates)
        return new_params, new_opt

    def apply_constraints(self, params, step):
        """MultiLayerNetwork.apply_constraints counterpart: the graph's
        apply_update has no constraint pass, so this is the identity —
        here so the distributed masters' sharded update can call ONE
        method on either net kind."""
        return params

    def make_train_step(self, donate=True, jit=True, with_health=False):
        def train_step(params, state, opt_state, inputs, labels, step, rng, mask=None):
            loss, new_state, grads = self.compute_gradients(
                params, state, inputs, labels, rng=rng, mask=mask)
            if with_health:
                # numerics-watchdog bundle, fused into the step (labels the
                # per-vertex series by vertex name)
                with jax.named_scope("health"):
                    health = _health.health_stats(grads, params, loss)
            new_params, new_opt = self.apply_update(params, opt_state, grads,
                                                    step)
            if with_health:
                return new_params, new_state, new_opt, loss, health
            return new_params, new_state, new_opt, loss

        if not jit:
            return train_step
        return jax.jit(_scopes.stamped(train_step),
                       donate_argnums=(0, 1, 2) if donate else ())

    def make_train_steps(self, k, donate=True, jit=True, with_health=False):
        """Fused K-step engine over the graph's train step: one
        ``lax.scan`` dispatch per K minibatches (nn/fused.py; dict-keyed
        inputs/labels stack leaf-wise; ``fit(steps_per_dispatch=K)``
        drives it)."""
        from deeplearning4j_tpu.nn import fused as _fused
        return _fused.make_train_steps(self, k, donate=donate, jit=jit,
                                       with_health=with_health)

    def _fit_batches(self, inputs, labels, batch_size, mask, pad_to=None):
        """Per-epoch (inputs, labels, mask) minibatch generator over the
        dict-keyed arrays; ``pad_to`` buckets every batch to the nominal
        batch size with the validity folded into the mask (exact under
        the masked-mean losses — shape bucketing, nn/fused.py)."""
        from deeplearning4j_tpu.datasets.iterator import pad_batch

        n = next(iter(inputs.values())).shape[0]
        bs = batch_size or n
        for i in range(0, n, bs):
            bi = {k: v[i:i + bs] for k, v in inputs.items()}
            bl = {k: v[i:i + bs] for k, v in labels.items()}
            bm = mask[i:i + bs] if mask is not None else None
            if pad_to:
                bi, bl, bm, _ = pad_batch(bi, bl, bm, bs)
            yield bi, bl, bm

    def fit(self, inputs, labels, *, epochs=1, batch_size=None, mask=None,
            steps_per_dispatch=1, pad_ragged=None):
        """Train over dict-keyed (or single-array) inputs/labels.
        ``steps_per_dispatch=K`` runs K steps per device dispatch through
        the fused ``lax.scan`` engine with prefetch + shape bucketing;
        ``pad_ragged=True`` buckets the K=1 loop's ragged tail batch
        (see MultiLayerNetwork.fit for both contracts)."""
        if self.params is None:
            self.init()
        if not isinstance(inputs, dict):
            inputs = {self.conf.inputs[0]: np.asarray(inputs)}
        if not isinstance(labels, dict):
            labels = {self.conf.outputs[0]: np.asarray(labels)}
        tm = self._time_major(inputs)
        use_tbptt = (self.conf.backprop_type == "tbptt" and tm is not None
                     and tm.shape[1] > self.conf.tbptt_fwd_length)
        k = int(steps_per_dispatch)
        if k > 1 or pad_ragged:
            # shape bucketing builds ONE validity mask; a graph mixing
            # pooled ([B, C]) and time-distributed ([B, T, C]) outputs
            # would leave the mismatched head silently unmasked — refuse
            # rather than break the exactness contract
            layouts = {("pooled" if v.ndim <= 2 else ("temporal",
                                                      v.shape[1]))
                       for v in labels.values()}
            if len(layouts) > 1:
                raise ValueError(
                    "shape bucketing (steps_per_dispatch > 1 / "
                    "pad_ragged) needs a single label layout; this graph "
                    "mixes pooled / differently-lengthed time-distributed "
                    "outputs — pad the dataset to the batch size yourself "
                    "or train with steps_per_dispatch=1")
        if k > 1:
            if use_tbptt:
                raise ValueError(
                    "steps_per_dispatch > 1 does not compose with TBPTT "
                    "(the chunk loop is its own on-device scan); use the "
                    "default single-step path")
            from deeplearning4j_tpu.nn import fused as _fused
            return _fused.fit_fused(
                self,
                lambda: self._fit_batches(inputs, labels, batch_size, mask),
                epochs=epochs, k=k, batch_size=batch_size)
        if use_tbptt:
            return self._fit_tbptt_loop(inputs, labels, batch_size, mask,
                                        pad_ragged, epochs)
        # the K=1 loop is the shared StepDriver (continuous/driver.py) —
        # the MLN fit-loop body exactly (one-step-late score fetch via
        # ScorePipeline, one-late health bundles, trace handoff, flight
        # records), now resumable between rounds for the
        # continuous-learning tier
        from deeplearning4j_tpu.continuous.driver import StepDriver
        drv = StepDriver(
            self,
            lambda: self._fit_batches(inputs, labels, batch_size, mask,
                                      pad_to=bool(pad_ragged)))
        return drv.run(epochs)

    def _fit_tbptt_loop(self, inputs, labels, batch_size, mask, pad_ragged,
                        epochs):
        """Whole-fit TBPTT: every minibatch runs the chunked on-device
        scan (``_fit_tbptt``) — its own loop because the chunk scan owns
        the RNG chain and score accumulation the StepDriver engines
        otherwise drive; one macro-batch = one recorded step, the MLN
        TBPTT-branch granularity."""
        reg, step_h, _etl_h, iters_c, score_g = _tm.train_metrics()
        try:
            with _tm.span("fit", net=type(self).__name__):
                for _ in range(epochs):
                    for l in self.listeners:
                        l.on_epoch_start(self)
                    for bi, bl, bm in self._fit_batches(
                            inputs, labels, batch_size, mask,
                            pad_to=bool(pad_ragged)):
                        t_tb = time.perf_counter()
                        with _tm.span("fit.step", tbptt=True):
                            tb_score = self._fit_tbptt(bi, bl, bm)
                        if reg.enabled:
                            step_h.observe(time.perf_counter() - t_tb)
                            iters_c.inc()
                            score_g.set(tb_score)
                    for l in self.listeners:
                        l.on_epoch_end(self)
                    self.epoch += 1
        except BaseException as e:
            _flight.crash_dump(e)
            raise
        finally:
            _listeners.run_fit_end_hooks(self)
        return self

    def output(self, inputs, mask=None):
        if self.params is None:
            self.init()
        if not isinstance(inputs, dict):
            inputs = {self.conf.inputs[0]: jnp.asarray(inputs)}
        outs, _ = self._jitted_apply()(self.params, self.state, inputs, mask)
        if len(self.conf.outputs) == 1:
            return outs[self.conf.outputs[0]]
        return outs

    @functools.lru_cache(maxsize=1)
    def _jitted_apply(self):
        def fwd(params, state, inputs, mask):
            return self.apply_fn(params, state, inputs, train=False, mask=mask)
        return jax.jit(fwd)

    def score(self, inputs, labels, mask=None):
        if self.params is None:
            self.init()
        if not isinstance(inputs, dict):
            inputs = {self.conf.inputs[0]: jnp.asarray(inputs)}
        loss, _ = self.loss_fn(self.params, self.state, inputs, labels,
                               train=False, mask=mask)
        return float(loss)

    def _eval_batches(self, data, labels, batch_size):
        """(x, y, mask) batches for the evaluate family: dict-keyed
        inputs/labels (the multi-input graph form iter_batches cannot
        slice) batch by slicing every entry in step; everything else goes
        through the shared iter_batches."""
        from deeplearning4j_tpu.datasets.iterator import iter_batches

        if isinstance(data, dict):
            n = next(iter(data.values())).shape[0]
            bs = batch_size or n
            for i in range(0, n, bs):
                bx = {k: v[i:i + bs] for k, v in data.items()}
                by = ({k: v[i:i + bs] for k, v in labels.items()}
                      if isinstance(labels, dict) else labels[i:i + bs])
                yield bx, by, None
            return
        yield from iter_batches(data, labels, batch_size, None)

    def evaluate(self, data, labels=None, *, batch_size=None,
                 evaluation=None, output_name=None):
        """Classification Evaluation over arrays, an (x, y) pair, dict
        inputs/labels (multi-input graphs), or any DataSetIterator
        (reference: ComputationGraph.evaluate(DataSetIterator);
        ``output_name`` selects a head on multi-output graphs)."""
        from deeplearning4j_tpu.eval.classification import Evaluation

        e = evaluation if evaluation is not None else Evaluation()
        head = output_name or self.conf.outputs[0]
        for bx, by, bm in self._eval_batches(data, labels, batch_size):
            out = self.output(bx, mask=bm)
            pred = out[head] if isinstance(out, dict) else out
            if isinstance(by, dict):
                by = by[head]
            e.eval(np.asarray(by), np.asarray(pred),
                   mask=None if bm is None else np.asarray(bm))
        return e

    def evaluate_regression(self, data, labels=None, *, batch_size=None,
                            output_name=None):
        """RegressionEvaluation (reference:
        ComputationGraph.evaluateRegression)."""
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation

        e = RegressionEvaluation()
        head = output_name or self.conf.outputs[0]
        for bx, by, bm in self._eval_batches(data, labels, batch_size):
            out = self.output(bx, mask=bm)
            pred = out[head] if isinstance(out, dict) else out
            if isinstance(by, dict):
                by = by[head]
            e.eval(np.asarray(by), np.asarray(pred),
                   mask=None if bm is None else np.asarray(bm))
        return e

    def evaluate_roc(self, data, labels=None, *, batch_size=None,
                     threshold_steps=0, output_name=None):
        """ROC / ROCMultiClass (reference: ComputationGraph.evaluateROC /
        evaluateROCMultiClass)."""
        from deeplearning4j_tpu.eval.roc import ROC, ROCMultiClass

        roc = None
        head = output_name or self.conf.outputs[0]
        for bx, by, bm in self._eval_batches(data, labels, batch_size):
            out = self.output(bx, mask=bm)
            pred = np.asarray(out[head] if isinstance(out, dict) else out)
            if isinstance(by, dict):
                by = by[head]
            if roc is None:
                roc = (ROC(threshold_steps) if pred.shape[-1] <= 2
                       else ROCMultiClass(threshold_steps))
            roc.eval(np.asarray(by), pred,
                     mask=None if bm is None else np.asarray(bm))
        if roc is None:
            raise ValueError("no data to evaluate")
        return roc

    def num_params(self):
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params))

    def add_listener(self, *ls):
        self.listeners.extend(ls)
        return self


class GraphBuilderModule:
    """SPI for reusable graph fragments (reference: nn/conf/module/
    GraphBuilderModule.java — "plugins and modules to generate configurations
    and layers"). Implementations append a named sub-graph (e.g. an
    inception block) to a GraphBuilder and return it, so model definitions
    compose from modules instead of repeating vertex boilerplate."""

    def module_name(self):
        """Lowercase module name, used to prefix generated layer names."""
        raise NotImplementedError

    def update_builder(self, builder, layer_name, input_size, config,
                       input_layer):
        """Append this module's layers to ``builder``.

        layer_name: base name for the generated vertices
        input_size: channel count of ``input_layer``'s activations
        config: module-specific structure (the reference passes int[][]
            filter-bank tables)
        input_layer: name of the vertex the module consumes
        Returns the builder (with the module's OUTPUT vertex added last, so
        callers can chain on builder's most recent name)."""
        raise NotImplementedError
