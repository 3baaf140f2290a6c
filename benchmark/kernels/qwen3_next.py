"""Operations a hybrid linear/softmax-attention mixture-of-experts decoder
(Qwen3-Next) requires per trained token: forward plus backward (three times
the forward's work), no recompute counted. Per layer, by its kind: the
gated delta rule's three projections (q, k, v, z fused; b, a; out) and the
recurrence by its definition, three products of a `[dk, dv]` state a value
head a token (the read `S^T k`, the rank-one write, the query `S^T q`), or
the gated attention's four projections (the query's doubled for the gate,
key and value at their own, narrower width) with causal scores and values
at what causality needs (each query sees on average half the keys); then
the router's product, the shared expert's three products and its gate, and
the routed experts' three at the EXPECTED number of assignments a token
has among the experts held here (`num_experts_per_tok` x held /
`num_experts`: the chip's share of the layer; the rows really routed are
the program's counters'). Left out, as not matrix work or under 0.1% of
the total: the embedding lookup, the norms, the rotation, the
convolution's four taps, SiLU, sigmoid, softplus, the decays, softmax,
top-k and the sort; and whatever the chunkwise form spends beyond the
recurrence's definition (its chunk-local inverse)."""


def train_flops_per_unit(model, traffic):
    d, v = model["n_embd"], model["vocab_size"]
    q_inner = model["n_head"] * model["head_dim"]
    kv_inner = model["n_kv_head"] * model["head_dim"]
    hv = model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    kw, vw = model["linear_num_key_heads"] * dk, hv * dv
    t = traffic["seq_len"]
    first, end = model["experts_held"]
    here = model["num_experts_per_tok"] * (end - first) / model["num_experts"]
    total = 2 * d * v                                   # the head
    for kind in model["layer_types"]:
        if kind == "linear_attention":
            total += 2 * d * (2 * kw + 2 * vw + 2 * hv) + 2 * vw * d
            total += 3 * 2 * hv * dk * dv               # read, write, query
        else:
            total += 2 * (3 * d * q_inner + 2 * d * kv_inner)  # q+gate, o
            total += 2 * 2 * q_inner * (t / 2)          # scores and values
        total += 2 * d * model["num_experts"]           # the router
        total += 2 * 3 * d * model["shared_expert_intermediate_size"] + 2 * d
        total += here * 2 * 3 * d * model["moe_intermediate_size"]
    return 3 * total
