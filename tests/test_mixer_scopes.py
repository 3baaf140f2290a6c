"""One grammar inside every mixer and the routed layer (nn/scopes.py,
grammar `s2`): directly inside a mixer's own scope its products stand
under `mix_in` or `mix_out`, its core under the scopes it had, grouped
key/value heads' repeat under `kv_repeat`, latent attention's joins under
`head_join`; inside `moe_experts` the weights' own traffic under
`moe_weights`. Read from the name stacks of the jaxpr of
`value_and_grad`, which are what the compiled `op_name`s are made of, at
toy size on the CPU and under the bfloat16 policy (under float32 the
weights' rounding is no operation at all). The seven metrics that read
the names (`benchmark/layer_metrics/`) are held to them here as well."""

import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import spec
from benchmark.readers import trace_scope_ms
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import scopes
from deeplearning4j_tpu.nn.conf import inputs as I
from deeplearning4j_tpu.utils import dtypes

D, T = 16, 128
PARTS = (scopes.MIX_IN, scopes.MIX_OUT)
# (mixer, its own scope, its core's scopes, the expansions it makes)
MIXERS = {
    "mha": (lambda: L.MultiHeadAttention(n_out=D, n_heads=4, causal=True),
            scopes.MHA, ("flash_attn.fwd", "flash_attn.bwd"), ()),
    "mha_grouped": (lambda: L.MultiHeadAttention(
        n_out=D, n_heads=4, n_kv_heads=2, head_dim=8, causal=True,
        bias=False, rope_theta=1e4, qk_norm=True, gate=True),
        scopes.MHA, ("flash_attn.fwd", "flash_attn.bwd"),
        (scopes.KV_REPEAT,)),
    "mla": (lambda: L.LatentAttention(
        n_out=D, n_heads=2, q_rank=8, kv_rank=8, nope_dim=8, rope_dim=8,
        v_dim=16, causal=True),
        "mla", ("flash_attn.fwd", "flash_attn.bwd"), (scopes.HEAD_JOIN,)),
    "gdn": (lambda: L.GatedDeltaNet(n_out=D, k_heads=1, v_heads=2,
                                    head_dim=8),
            "gdn", ("gdn_conv", "gdn_core"), ()),
    "ssm": (lambda: L.Mamba2Mixer(n_out=D, heads=4, head_dim=8, groups=2,
                                  state=8, chunk=32),
            "ssm", ("ssm_conv", "ssd_core"), ()),
    "short_conv": (lambda: L.ShortConv(n_out=D, kernel=3),
                   "short_conv", (), ()),
}
ROUTED = ["lfm2-train-t8192", "qwen3next-train-t4096",
          "nemotron3nano-train-packed", "glm47flash-train-t4096",
          "sdar-train-bd4-t4096"]
# ISSUE 54 lists the seven language cells for the first four; the seventh,
# `ouro-train-t2048`, is left off their lists: a metric that lists the
# four oldest language cells breaks `len(shared) == 18` in
# tests/benchmark_suite/test_nemotron3nano.py, which this PR may not edit
# (PERF.md section 7; ROADMAP D12 queues the cell for the lists)
LANGUAGE = ["gpt2m-train-t1024", *ROUTED]
NEW_METRICS = {
    "mixer_ms.tokens": LANGUAGE, "mixer_in_ms.tokens": LANGUAGE,
    "mixer_out_ms.tokens": LANGUAGE, "mlp_ms.tokens": LANGUAGE,
    # every cell whose attention repeats key/value heads or joins a head
    "kv_expand_ms.tokens": ROUTED, "moe_permute_ms.tokens": ROUTED,
    "moe_weights_ms.tokens": ROUTED}


def _walk(jaxpr, outer="", out=None):
    """[(path, equation)] of a jaxpr and of the jaxprs inside it, a path
    the name stacks from the outermost equation down, as the lowering
    joins them into `op_name` (a kernel's own body is not entered)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        path = "/".join(p for p in (outer, str(eqn.source_info.name_stack))
                        if p)
        out.append((path, eqn))
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _walk(sub, path, out)
    return out


def _holds(path, names):
    return any(trace_scope_ms.matcher({"scope": re.escape(n)})(path)
               for n in names)


def _grad_paths(block, it, x):
    p = block.init(jax.random.PRNGKey(0), it)
    state = block.init_state(it)

    def loss(p, x):
        return jnp.sum(block.apply(p, state, x, train=True)[0]
                       .astype(jnp.float32) ** 2)

    try:
        dtypes.bf16_policy()
        jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
            p, x).jaxpr
    finally:
        dtypes.f32_policy()
    return _walk(jaxpr)


def test_every_mixer_the_blocks_can_hold_is_a_case_here():
    """A mixer is a layer that says where a block keeps its parameters
    (`param_key`): one added later joins `MIXERS` with its scope, or this
    fails; once there, the test below holds its products to the
    grammar."""
    mixers = {c for c in vars(L).values()
              if isinstance(c, type) and hasattr(c, "param_key")}
    assert mixers == {type(make()) for make, *_ in MIXERS.values()}


@pytest.mark.parametrize("case", MIXERS)
def test_a_mixers_products_stand_under_the_four_parts(case, kernel_dispatch):
    make, own, cores, expansions = MIXERS[case]
    block = L.TransformerBlock(n_out=D, mixer=make(), norm="rms",
                               bias=False, ffn="gated", ffn_width=32,
                               activation="silu")
    x = jnp.ones((2, T, D), jnp.float32)
    with kernel_dispatch():     # the flash kernels, as the chip takes them
        rows = _grad_paths(block, I.RecurrentType(D, T), x)
    inside = [(p, e) for p, e in rows if _holds(p, [own])]
    dots = [p for p, e in inside if e.primitive.name == "dot_general"]
    # (a) no product of the mixer outside its parts and its core
    assert dots and all(_holds(p, PARTS + cores) for p in dots), \
        [p for p in dots if not _holds(p, PARTS + cores)]
    for part in PARTS:
        for backward in (False, True):
            assert any(_holds(p, [part]) and ("transpose(" in p) == backward
                       for p in dots), (part, backward)
    # a part stands directly inside the mixer's own scope, and the
    # mixer's inside the block's `attn`
    for p in dots:
        if _holds(p, PARTS):
            assert re.search(r"attn\)*/" + own + r"/mix_(in|out)(/|$)", p), p
    # the core kept its names
    kernels = [p for p, e in inside if e.primitive.name == "pallas_call"]
    assert all(_holds(p, cores) for p in kernels), kernels
    if "flash_attn.fwd" in cores:
        assert kernels
    # (b) the expansions, where the mixer makes one and nowhere else
    everywhere = [p for p, _ in rows]
    for name in (scopes.KV_REPEAT, scopes.HEAD_JOIN):
        named = [p for p in everywhere if _holds(p, [name])]
        assert bool(named) == (name in expansions), (name, named[:3])
        assert all(_holds(p, [own]) for p in named)
        if named:   # autodiff's sum over a group, the slices of a join
            assert {("transpose(" in p) for p in named} == {False, True}
    # the dense FFN's products are the block's `mlp`, no mixer's
    mlp = [p for p, e in rows if e.primitive.name == "dot_general"
           and _holds(p, ["mlp"])]
    assert len(mlp) == 9 and not any(_holds(p, [own, *PARTS]) for p in mlp)


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["kept", "recomputed"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_the_experts_weights_traffic_stands_under_moe_weights(gated,
                                                              recompute):
    """(c) `moe_weights` holds every rounding of a held expert's weights
    and the join of gate with up, in the forward, in the forward made
    again and in the backward rule, and no kernel; nothing else of
    `moe_experts` touches a weights-shaped array but the kernels and the
    weight gradients' own handling."""
    held, f = 4, 32
    block = L.TransformerBlock(
        n_out=D, mixer=None, activation="silu", norm="rms", bias=False,
        ffn="moe", ffn_width=f, n_experts=8, top_k=2, experts_held=(2, 6),
        router="softmax", expert_gated=gated, recompute_moe=recompute)
    rows = _grad_paths(block, I.RecurrentType(D, 8),
                       jnp.ones((2, 8, D), jnp.float32))
    named = [(p, e) for p, e in rows if _holds(p, [scopes.MOE_WEIGHTS])]
    assert named and all(_holds(p, ["moe_experts"]) and _holds(p, ["mlp"])
                         for p, _ in named)
    assert {e.primitive.name for _, e in named} <= {
        "convert_element_type", "concatenate", "pjit", "jit"}
    weights = {(held, D, f), (held, D, 2 * f), (held, f, D)}

    def rounds(e):     # a float32 weights-shaped array made bfloat16
        return (e.primitive.name == "convert_element_type"
                and e.invars[0].aval.shape in weights
                and e.invars[0].aval.dtype == jnp.float32
                and e.outvars[0].aval.dtype == jnp.bfloat16)

    everywhere = [(p, e) for p, e in rows if rounds(e) or (
        e.primitive.name == "concatenate"
        and e.outvars[0].aval.shape in weights)]
    assert all(_holds(p, [scopes.MOE_WEIGHTS]) for p, _ in everywhere), \
        [p for p, _ in everywhere if not _holds(p, [scopes.MOE_WEIGHTS])]
    # forward: gate ‖ up and down rounded; backward: down, then the join
    # again; the forward made again repeats the forward's
    passes = 2 + recompute
    assert sum(rounds(e) for _, e in everywhere) == 2 * passes
    assert sum(e.primitive.name == "concatenate"
               for _, e in everywhere) == (passes if gated else 0)
    backward = [p for p, _ in named if "transpose(" in p]
    assert backward and len(backward) < len(named)
    # the kernels stay outside it, under `moe_experts`
    kernels = [p for p, e in rows if e.primitive.name == "pallas_call"
               and _holds(p, ["moe_experts"])]
    assert len(kernels) == 8 + 3 * recompute
    assert not any(_holds(p, [scopes.MOE_WEIGHTS]) for p in kernels)


def test_the_names_are_safe_and_each_metric_matches_its_own_alone():
    """(d) every new name passes `scopes.safe` unchanged, and the matcher
    of each new metric file matches a path built from its names and none
    built from its neighbours'."""
    names = (scopes.MHA, scopes.MIX_IN, scopes.MIX_OUT, scopes.KV_REPEAT,
             scopes.HEAD_JOIN, scopes.MOE_WEIGHTS)
    assert all(scopes.safe(n) == n for n in names)
    assert len(set(names)) == len(names)

    def path(*inner, backward=False):
        stack = "jvp(L03.TransformerBlock)"
        if backward:
            stack = f"transpose({stack})"
        return f"jit(train_step_s2)/{stack}/" + "/".join(inner) + "/mul:"

    mixers = ("mha", "mla", "gdn", "ssm", "short_conv")
    own = {
        "mixer_ms.tokens": [path("attn", m) for m in mixers]
        + [path("attn", "mla", "rope", backward=True)],
        "mixer_in_ms.tokens": [path("attn", m, scopes.MIX_IN)
                               for m in mixers],
        "mixer_out_ms.tokens": [path("attn", m, scopes.MIX_OUT,
                                     backward=True) for m in mixers],
        "kv_expand_ms.tokens": [path("attn", "mha", scopes.KV_REPEAT),
                                path("attn", "mla", scopes.HEAD_JOIN)],
        "mlp_ms.tokens": [path("mlp"), path("mlp", "moe", "moe_route"),
                          "jit(train_step_s2)/jvp(loss)/mtp/mlp/rmsnorm/x:"],
        "moe_permute_ms.tokens": [path("mlp", "moe", "moe_route",
                                       "moe_permute", backward=True)],
        "moe_weights_ms.tokens": [path("mlp", "moe", "moe_experts",
                                       scopes.MOE_WEIGHTS)],
    }
    others = [path("attn", "rmsnorm"), path("attn", "mlai"),
              path("attn", "mha_2"), path("mlp_Wg"), path("my_mlp"),
              path("attn", "mix_input"), path("attn", "mix_outer"),
              path("moe", "moe_experts", "moe_weights_out"),
              path("moe", "moe_permuted"), path("attn", "kv_repeated"),
              "params['L03']['mlp_Wg']", "jit(train_step_s2)/updater/mul:"]
    assert set(own) == set(NEW_METRICS)
    for name, paths in own.items():
        lm = spec.layer_metric(name)
        assert lm["reader"] == "trace_scope_ms", name
        match = trace_scope_ms.matcher(lm["args"])
        assert all(match(p) for p in paths), name
        for other, theirs in own.items():
            # the whole mixer holds its parts, `mlp` the routed layer
            holds = (name == "mixer_ms.tokens" and other in (
                "mixer_in_ms.tokens", "mixer_out_ms.tokens",
                "kv_expand_ms.tokens")) or (
                name == "mlp_ms.tokens" and other.startswith("moe_"))
            if other != name and not holds:
                assert not any(match(p) for p in theirs), (name, other)
        assert not any(match(p) for p in others), name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_goes_to_its_cells_and_no_other(name):
    bench = spec.load_benchmark()
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model step",
        "moves": "train_tokens_per_s", "workloads": NEW_METRICS[name]}
    reporting = [c["name"] for c in bench["workloads"]
                 if entry in spec.cell_metrics(bench, c["name"],
                                               "per_layer")]
    assert sorted(reporting) == sorted(NEW_METRICS[name])
