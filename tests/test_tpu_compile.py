"""The flash kernels compiled for a described v5e, Mosaic and XLA:TPU and
all, with no chip: what lowering alone (test_ops.py's
TestDefaultDispatchKernelsLowerForTpu) cannot show is whether Mosaic takes
the backward in the VMEM ``_run_bwd_local`` asks for, which is what chooses
the form (ISSUE 46), and what XLA keeps of q, k and v around the two calls
of a whole attention layer (ISSUE 47). Two to five seconds a case.

The TPU's library is loaded inside a fixture and by this file alone: one
process at a time may hold it, so nothing here runs at import or at
collection, and every compile is in the test's own process."""

import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.conf import inputs
from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
from deeplearning4j_tpu.nn.layers.mixers.latent_attention import \
    LatentAttention
from deeplearning4j_tpu.ops import attention_pallas
from deeplearning4j_tpu.utils import dtypes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as jcc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jcc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    jcc.reset_cache()


def _compiled_backward(one_chip, bh, t, d, dtype, form=None, geometry=None):
    """The compiled text of one backward (causal, or under ``geometry``)
    for a caller in ``dtype`` at the dispatch's blocks, in the form
    ``_run_bwd`` chooses or, with ``form``, in that one. The residuals q,
    k, v are what the forward kernel read (``_operand_dtype``); out and g
    are the caller's."""
    r = jax.ShapeDtypeStruct(
        (bh, t, d), attention_pallas._operand_dtype(dtype, False),
        sharding=one_chip)
    x = jax.ShapeDtypeStruct((bh, t, d), dtype, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((bh, t), jnp.float32, sharding=one_chip)
    causal = geometry is None

    def bwd(q, k, v, out, lse, g):
        if form is None:
            return attention_pallas._run_bwd(
                (q, k, v, None, out, lse), g, None, 1, causal, d ** -0.5,
                512, 512, False, geometry)
        return attention_pallas._run_bwd_local(
            q, k, v, out, lse, g, None, None, 1, causal, d ** -0.5, 512, 512,
            False, form, geometry)
    # the chip runs with 32-bit defaults; conftest's float64 mode would put
    # f64 constants into the kernel body, which Mosaic refuses to cast
    with jax.enable_x64(False):
        return jax.jit(bwd).lower(r, r, r, x, lse, x).compile().as_text()


@pytest.mark.parametrize("bh,t,d,dtype,kernels", [
    # the cells' calls: the two at width 256 ask Mosaic for 20.06 MiB
    (20, 4096, 256, jnp.float32, ("fused",)),     # glm47flash-train-t4096
    (16, 4096, 256, jnp.float32, ("fused",)),     # qwen3next-train-t4096
    (32, 8192, 64, jnp.float32, ("fused",)),      # lfm2: 14.31 MiB, unasked
    (32, 4096, 128, jnp.float32, ("fused",)),     # nemotron3nano
    (16, 2048, 256, jnp.float32, ("fused",)),     # chip_smoke.py's 3 layers
    (16, 4096, 256, jnp.bfloat16, ("fused",)),    # 14.06 MiB, unasked
    (32, 8192, 128, jnp.float32, ("fused",)),     # 16.56 MiB, asked
    # past the budget
    (8, 8192, 256, jnp.float32, ("dkv", "dq")),
    (8, 16384, 64, jnp.float32, ("dkv", "dq")),
])
def test_the_chosen_backward_compiles_for_the_chip(
        one_chip, no_compile_cache, bh, t, d, dtype, kernels):
    text = _compiled_backward(one_chip, bh, t, d, dtype)
    assert text.count("tpu_custom_call") >= len(kernels)
    for form in ("fused", "dkv", "dq"):
        assert ("flash_attn_bwd_" + form in text) == (form in kernels), form


@pytest.mark.parametrize("bh,t,d,geometry,live", [
    # sdar-train-bd4-t4096's call: 80 of a head's 256 tiles
    (32, 8192, 128, attention_pallas.BlockDiffusion(4096, 4), 80),
    # glm47flash's: the backward asks Mosaic for VMEM beside the list
    (20, 4096, 256, None, 36),
    (32, 8192, 64, None, 136),                    # lfm2-train-t8192's
], ids=["sdar", "glm47flash", "lfm2"])
def test_both_kernels_compile_under_the_step_list(
        one_chip, no_compile_cache, bh, t, d, geometry, live):
    """ISSUE 53: the forward and the fused backward on a grid of (heads,
    live steps), the list scalar-prefetched, through Mosaic and XLA:TPU at
    the cells' shapes."""
    r = jax.ShapeDtypeStruct((bh, t, d), jnp.bfloat16, sharding=one_chip)
    steps = attention_pallas.step_list(
        geometry is None, geometry, t // 512, t // 512, 512, 512)
    assert (steps.live, steps.rectangle) == (live, (t // 512) ** 2)

    def fwd(q, k, v):
        return attention_pallas._run_fwd(
            q, k, v, None, 1, geometry is None, d ** -0.5, 512, 512, False,
            jnp.float32, geometry)
    with jax.enable_x64(False):
        text = jax.jit(fwd).lower(r, r, r).compile().as_text()
    name = "flash_attn_fwd" if geometry is None else "flash_attn_bd_fwd"
    assert "tpu_custom_call" in text and name in text
    text = _compiled_backward(one_chip, bh, t, d, jnp.float32,
                              geometry=geometry)
    assert ("flash_attn_bwd_fused" if geometry is None
            else "flash_attn_bd_bwd_fused") in text
    assert "tpu_custom_call" in text


def test_mosaic_refuses_the_width_256_backward_at_its_default(
        one_chip, no_compile_cache, monkeypatch):
    """The control: without the ask the compiler refuses the call the two
    width-256 cells make, which is why the rule used to split it."""
    monkeypatch.setattr(attention_pallas, "_VMEM_DEFAULT", 1 << 40)
    # 12 heads: ``_run_bwd_local`` keeps its traces by shape, and the
    # cells' shapes above were traced with the ask
    with pytest.raises(Exception, match="(?i)vmem"):
        _compiled_backward(one_chip, 12, 4096, 256, jnp.float32, "fused")


@pytest.fixture
def on_the_chip(monkeypatch):
    """The dispatch as the cells meet it: the backend gate open and the
    TPU training policy (bfloat16 products, float32 results)."""
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
    dtypes.bf16_policy()
    yield
    dtypes.f32_policy()


@pytest.mark.parametrize("layer,shape,heads", [
    # one layer of gpt2m-train-t1024: [4, 1024, 1024], 16 heads of 64
    (MultiHeadAttention(n_out=1024, n_heads=16, causal=True),
     (4, 1024, 1024), (64, 1024, 64)),
    # one of glm47flash-train-t4096: [1, 4096, 2048], 20 heads of 192 + 64
    (LatentAttention(n_out=2048, n_heads=20, q_rank=768, kv_rank=512,
                     nope_dim=192, rope_dim=64, v_dim=256, causal=True,
                     rope_theta=1e6, norm_eps=1e-5),
     (1, 4096, 2048), (20, 4096, 256)),
], ids=["gpt2m", "glm47flash"])
def test_a_layer_keeps_one_rounded_copy_of_its_heads(
        one_chip, no_compile_cache, on_the_chip, layer, shape, heads):
    """Forward and backward of one attention layer from float32
    activations: the forward kernel reads no float32 head array, and under
    ``flash_attn.bwd`` nothing but the cotangent is rounded (the parent
    rounded q, k and v there again, beside the float32 copies the forward
    read)."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda key: layer.init(
                key, inputs.RecurrentType(shape[2], shape[1])),
            jax.random.PRNGKey(0)))

    def loss(params, x):
        y, _ = layer.apply(params, {}, x, train=True)
        return jnp.sum(y * y)
    with jax.enable_x64(False):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, sds(shape)).compile().as_text()
    head = "[" + ",".join(map(str, heads)) + "]"
    (fwd,) = [ln for ln in text.splitlines()
              if ln.lstrip().startswith("%flash_attn_fwd")
              and "tpu_custom_call" in ln]
    operands = fwd.split("operand_layout_constraints=")[1].split(
        "frontend_attributes")[0]
    assert operands.count("bf16" + head) == 3, operands
    assert "f32" + head not in operands, operands
    assert fwd.lstrip().split(" = ")[1].startswith("(f32" + head), fwd
    rounded = [ln for ln in text.splitlines()
               if re.search(r"= bf16" + re.escape(head) + r"\S* convert\(", ln)
               and "flash_attn.bwd" in ln]
    assert len(rounded) <= 1, rounded


@pytest.mark.parametrize("m,d,f,groups,rows,act", [
    (65536, 2048, 768, 16, 512, "silu"),      # sdar-train-bd4-t4096
    (40960, 2048, 512, 16, 80, "silu"),       # qwen3next-train-t4096
    (32768, 2048, 1536, 8, 512, "silu"),      # lfm2-train-t8192
    (16384, 2048, 1536, 8, 256, "silu"),      # glm47flash-train-t4096
    (24576, 2688, 1856, 16, 192, None),       # nemotron3nano: no gate, ReLU^2
], ids=["sdar", "qwen3next", "lfm2", "glm47flash", "nemotron3nano"])
def test_the_expert_ffn_compiles_for_the_chip(
        one_chip, no_compile_cache, monkeypatch, m, d, f, groups, rows, act):
    """Forward and backward of a cell's expert FFN (ISSUE 50): Mosaic takes
    both activation kernels at the tile and in the VMEM ``act_vmem_bytes``
    counts, an f that is no whole number of lane tiles included (1856),
    and the compiled text holds no operation over every slot of a sorted
    buffer outside a kernel."""
    from deeplearning4j_tpu.nn import activations
    from deeplearning4j_tpu.ops import expert_ffn
    monkeypatch.setattr(attention_pallas, "backend_is_tpu", lambda: True)
    gated = act is not None
    fn = activations.get(act or "relu2")
    shape = lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)

    def loss(xs, w_gate, w_up, w_down, sizes, cot):
        return jnp.sum(cot * expert_ffn.expert_ffn(
            xs, w_gate, w_up, w_down, sizes, fn, jnp.float32, rows))

    with jax.enable_x64(False):
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 2, 3))).lower(
            shape((m, d), jnp.bfloat16),
            shape((groups, d, f)) if gated else None, shape((groups, d, f)),
            shape((groups, f, d)), shape((groups,), jnp.int32),
            shape((m, d))).compile().as_text()
    for kernel in ("moe_act_fwd", "moe_act_bwd"):
        assert kernel in text
    assert text.count("tpu_custom_call") >= 8
    tile = expert_ffn._act_tile(m, f, gated, 2)
    assert tile in (256, 512)
    assert expert_ffn.act_vmem_bytes(tile, f, gated, 2) <= expert_ffn._VMEM
    # what XLA is left with between the kernels: nothing on [m, f | 2 f]
    assert not re.search(
        rf"= (?:bf16|f32)\[{m},(?:{f}|{2 * f})\][^=]* fusion\(", text)
