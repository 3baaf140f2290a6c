"""Operations a mixture-of-experts decoder trained by block diffusion
(SDAR-30B-A3B-Chat, `sdar_moe`) requires per trained token: forward plus
backward (three times the forward's matrix work), no recompute counted
(what the program makes again in its backward pass is its own business).
A token is ONE id of the batch; the step carries it at two positions, the
noised copy's and the clean copy's, so everything a layer does a position
counts twice a token: the attention's four projections, the router's
product and the experts' three at the EXPECTED number of assignments a
position has among the experts held here (`num_experts_per_tok` x held /
`num_experts`: the chip's share of the layer; the rows really routed are
the program's counters'). The scores and values at what the mask needs:
of the (2T)^2 pairs a sequence, T^2 + T L are live (`kernels/
block_diffusion_attn.py`), T + L keys a token. The head runs over the
noised copy's rows alone, once a token. Everything of the clean copy's
last layer is counted, as the configuration's `departures` say. Left out,
as not matrix work or under 0.1% of the total: the embedding lookup, the
draw, RMSNorm, the rotations, SiLU, softmax, top-k and the sort."""

from benchmark.kernels import block_diffusion_attn


def forward_parts_per_token(model, traffic):
    """{part: operations a token of the forward pass}."""
    d, v = model["n_embd"], model["vocab_size"]
    q_inner = model["n_head"] * model["head_dim"]
    kv_inner = model["n_kv_head"] * model["head_dim"]
    first, end = model["experts_held"]
    here = model["num_experts_per_tok"] * (end - first) / model["num_experts"]
    layers = len(model["layer_types"])
    pairs = block_diffusion_attn.live_pairs(
        traffic["seq_len"], model["block_length"]) / traffic["seq_len"]
    return {
        "attn_projections": layers * 2 * 2 * (2 * d * q_inner
                                              + 2 * d * kv_inner),
        "attn_scores": layers * 2 * 2 * q_inner * pairs,
        "routers": layers * 2 * 2 * d * model["num_experts"],
        "held_experts": layers * 2 * here * 2 * 3 * d
        * model["moe_intermediate_size"],
        "head": 2 * d * v}


def train_flops_per_unit(model, traffic):
    return 3 * sum(forward_parts_per_token(model, traffic).values())
