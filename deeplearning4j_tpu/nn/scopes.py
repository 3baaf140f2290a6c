"""The names the train step's device operations carry (`jax.named_scope`).

A scope changes HLO metadata only (`op_name`): nothing on the device, and
jax leaves metadata out of the persistent cache's key. The profiler's
trace shows the path per operation (`tf_op`), and JAX wraps the backward
pass's part of it in `transpose(jvp(...))`, so one scope names a layer's
forward and its backward. The grammar (PERF.md section 3 lists who reads
what): `L<ii>.<LayerClass>` per MultiLayerNetwork layer,
`V.<vertex>.<Class>` per ComputationGraph vertex, `attn` / `mlp` inside a
transformer block (`short_conv` inside `attn` where that is the mixer,
or `gdn` with `gdn_conv` and `gdn_core` inside it where the mixer is the
gated delta rule: `gdn_conv` holds the taps and SiLU, which with
`short_conv`'s gates and taps are the kernels `causal_conv_fwd` and,
under `transpose(`, `causal_conv_bwd` of ops/causal_conv.py, each with
the taps' transposition beside it; or `ssm` with `ssm_conv` and `ssd_core`
inside it where the mixer is Mamba-2: `ssm_conv` holds the taps with
their bias and SiLU, the same two kernels, `ssd_core` the selective scan
in its chunkwise form, ops/ssd.py; or `mla` where the mixer is latent
attention: the projections, the latent norms, the rotations and the
flash kernels' own scopes inside it; `attn_gate` around gated attention's
output gate; a block whose mixer or FFN is absent has no `attn` or no
`mlp`; `moe`
inside `mlp` where the FFN is routed experts, with `moe_route` and
`moe_experts` inside it and `moe_shared` beside them where the block has
a shared expert), `loss` (with `lm_head` around a multi-token head's
product and cross-entropy, and `mtp` around its module: the block's own
`attn` / `mlp` scopes and the second `lm_head` inside it), `grad_norm`,
`updater`, `health`, and
`<kernel>.fwd` / `<kernel>.bwd` around the Pallas kernels. Anything
outside `[A-Za-z0-9_.-]` in a name becomes `_`.

**Inside every mixer the same four parts** (grammar `s2`; the names are
the constants below and are spelled nowhere else in the program).
Directly inside the mixer's own scope (`MHA` around all of
`MultiHeadAttention.apply`; `mla`, `gdn`, `ssm`, `short_conv`):
`MIX_IN`, every product that reads the mixer's input or a latent made
from it, with its bias, before the core (`Wqkv` or `Wq` and `Wkv`;
`W_qa`, `W_qb`, `W_kva`, `W_kvb`, the latent norms between them outside
it; `W_qkvz` and `W_ba`; the three products from Mamba-2's `W_in`; the
short convolution's `W_in`); the core under the scopes it had
(`flash_attn.fwd` / `.bwd`, `gdn_conv`, `gdn_core`, `ssm_conv`,
`ssd_core`, the `causal_conv_*` kernels of `short_conv`); `MIX_OUT`, the
out-projection and its bias; `KV_REPEAT` around the repeat of grouped
key/value heads to the query heads' number (autodiff's sum over a group
lands under `transpose(` of the same scope) and `HEAD_JOIN` around latent
attention's concatenations of a head's two parts with the shared rotary
key's broadcast. What is left directly under the mixer's scope is its
glue (norms, `rope`, gates, decays, relayouts, the mask's product) and is
read by subtraction. A mixer added later names its products so, or
`tests/test_mixer_scopes.py` fails. In the routed layer `MOE_WEIGHTS`,
inside `moe_experts`, holds the work that touches the held experts'
weights and no row: the float32 leaves rounded to the compute dtype and
gate joined with up, in the forward rule, in the forward made again
under `recompute_moe` and in the backward rule; the grouped kernels, the
two activation kernels and the weight gradients stay outside it.
"""

from __future__ import annotations

import re

import jax

_UNSAFE = re.compile(r"[^A-Za-z0-9_.-]")

#: The grammar's version, stamped on the jitted step functions' names.
#: jax leaves `op_name` out of the persistent compile cache's key, so a
#: step whose computation did not change is loaded as an older program
#: cached it, with that program's names: ResNet-50's first traced run of
#: PR 25 read `transpose(jvp())` where `transpose(jvp(V.x.Conv))` had
#: been lowered (PERF.md section 6). A jitted function's name is in the
#: key. Raise this when a scope is added, renamed or moved: every step
#: then compiles anew once, and profiles read the names of the code that
#: runs.
GRAMMAR = "s2"

#: The parts a mixer names inside its own scope, plain attention's own
#: scope, and the routed experts' weights' traffic (the docstring has what
#: each holds): `with jax.named_scope(scopes.MIX_IN):` at the call site.
MHA = "mha"
MIX_IN = "mix_in"
MIX_OUT = "mix_out"
KV_REPEAT = "kv_repeat"
HEAD_JOIN = "head_join"
MOE_WEIGHTS = "moe_weights"


def safe(name):
    return _UNSAFE.sub("_", str(name))


def stamped(step_fn):
    """`step_fn` (about to be jitted) named `<name>_<GRAMMAR>`."""
    step_fn.__name__ = step_fn.__qualname__ = \
        f"{step_fn.__name__}_{GRAMMAR}"
    return step_fn


def layer(i, layer_conf):
    """Scope of layer ``i`` of a MultiLayerNetwork."""
    return jax.named_scope(f"L{i:02d}.{safe(type(layer_conf).__name__)}")


def vertex(name, vertex_obj):
    """Scope of one ComputationGraph vertex: a LayerVertex is named by
    the class of the layer it holds, any other vertex by its own."""
    inner = getattr(vertex_obj, "layer", None)
    cls = type(vertex_obj if inner is None else inner).__name__
    return jax.named_scope(f"V.{safe(name)}.{safe(cls)}")
