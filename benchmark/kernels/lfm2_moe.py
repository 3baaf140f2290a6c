"""Operations a hybrid conv/attention mixture-of-experts decoder (LFM2-MoE)
requires per trained token: forward plus backward (three times the
forward's matrix work), no recompute counted. Per layer, by its kind: the
gated short convolution's two projections or the attention's four (the
key/value projections at their own, narrower width) with causal scores and
values at what causality needs (each query sees on average half the keys);
the dense gated FFN's three products, or the router's product and the
experts' three at the EXPECTED number of assignments a token has among the
experts held here (`num_experts_per_tok` x held / `num_experts`: the chip's
share of the layer; the rows really routed are the program's counters').
Left out, as not matrix work or under 0.1% of the total: the embedding
lookup, RMSNorm, the rotations, the convolution's taps, SiLU, softmax,
sigmoid, top-k and the sort."""


def train_flops_per_unit(model, traffic):
    d, v = model["n_embd"], model["vocab_size"]
    q_inner = model["n_head"] * model["head_dim"]
    kv_inner = model["n_kv_head"] * model["head_dim"]
    t = traffic["seq_len"]
    first, end = model["experts_held"]
    here = model["num_experts_per_tok"] * (end - first) / model["num_experts"]
    total = 2 * d * v                                   # the head
    for i, kind in enumerate(model["layer_types"]):
        if kind == "conv":
            total += 2 * (d * 3 * d + d * d)            # in_proj, out_proj
        else:
            total += 2 * (2 * d * q_inner + 2 * d * kv_inner)   # q, o; k, v
            total += 2 * 2 * q_inner * (t / 2)          # scores and values
        if i < model["num_dense_layers"]:
            total += 2 * 3 * d * model["intermediate_size"]
        else:
            total += 2 * d * model["num_experts"]       # the router
            total += here * 2 * 3 * d * model["moe_intermediate_size"]
    return 3 * total
