"""Operations a latent-attention mixture-of-experts decoder with a
multi-token-prediction module (GLM-4.7-Flash, `glm4_moe_lite`) requires per
trained token: forward plus backward (three times the forward's matrix
work), no recompute counted (the two heads' second forward under
`jax.checkpoint` is the program's own business). `layer_types` lists the
blocks that run, the trunk's and then the module's: each the latent
attention's five projections with causal scores and values at what
causality needs (`kernels/mla.py`), then the dense gated FFN's three
products (the first `num_dense_layers`) or the router's product, the shared
expert's three and the routed experts' three at the EXPECTED number of
assignments a token has among the experts held here
(`num_experts_per_tok` x held / `num_experts`: the chip's share of the
layer; the rows really routed are the program's counters'). The module
adds its `[2d, d]` joining product and the head a second time. Left out,
as not matrix work or under 0.1% of the total: the two embedding lookups,
the norms, the rotations, SiLU, softmax, sigmoid, top-k and the sort."""

from benchmark.kernels import mla


def forward_parts_per_token(model, traffic):
    """{part: operations a token of the forward pass}."""
    d, v = model["n_embd"], model["vocab_size"]
    first, end = model["experts_held"]
    here = model["num_experts_per_tok"] * (end - first) / model["num_experts"]
    proj, attn = mla.forward_flops_per_token(
        traffic["seq_len"], d, model["n_head"], model["q_lora_rank"],
        model["kv_lora_rank"], model["qk_nope_head_dim"],
        model["qk_rope_head_dim"], model["v_head_dim"])
    blocks = len(model["layer_types"])
    dense = model["num_dense_layers"]
    fe = model["moe_intermediate_size"]
    modules = model["num_nextn_predict_layers"]
    return {
        "mla_projections": blocks * proj,
        "mla_scores": blocks * attn,
        "dense_ffn": dense * 2 * 3 * d * model["intermediate_size"],
        "routers": (blocks - dense) * 2 * d * model["num_experts"],
        "shared_experts": (blocks - dense) * 2 * 3 * d * fe,
        "held_experts": (blocks - dense) * here * 2 * 3 * d * fe,
        "heads": (1 + modules) * 2 * d * v,
        "mtp_join": modules * 2 * 2 * d * d}


def train_flops_per_unit(model, traffic):
    return 3 * sum(forward_parts_per_token(model, traffic).values())
