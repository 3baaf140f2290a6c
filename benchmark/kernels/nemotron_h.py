"""Operations a hybrid Mamba-2 / attention mixture-of-experts decoder
(Nemotron-H) requires per trained token: forward plus backward (three
times the forward's work), no recompute counted. Every layer is one part,
by `pattern`: `M` the Mamba-2 mixer's two projections (z, x, B, C and dt
fused; out) and the recurrence at the chunkwise form's count
(`kernels/ssd.py`); `*` the attention's four projections (key and value at
their own, narrower width) with causal scores and values at what causality
needs (each query sees on average half the keys); `E` the router's
product, the shared expert's two products and the routed experts' two at
the EXPECTED number of assignments a token has among the experts held here
(`num_experts_per_tok` x held / `num_experts`: the chip's share of the
layer; the rows really routed are the program's counters'). Left out, as
not matrix work or under 0.1% of the total: the embedding lookup, the
norms, the convolution's four taps and bias, SiLU, ReLU^2, sigmoid,
softplus, the decays, top-k and the sort."""

from benchmark.kernels import ssd


def train_flops_per_unit(model, traffic):
    d, v = model["n_embd"], model["vocab_size"]
    q_inner = model["n_head"] * model["head_dim"]
    kv_inner = model["n_kv_head"] * model["head_dim"]
    hs, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["n_groups"], model["ssm_state_size"]
    inner = hs * p
    t = traffic["seq_len"]
    first, end = model["experts_held"]
    here = model["num_experts_per_tok"] * (end - first) / model["num_experts"]
    total = 2 * d * v                                   # the head
    for kind in model["pattern"]:
        if kind == "M":
            total += 2 * d * (2 * inner + 2 * g * n + hs) + 2 * inner * d
            total += ssd.forward_flops_per_token(
                hs, p, g, n, model["chunk_size"], t)
        elif kind == "*":
            total += 2 * (2 * d * q_inner + 2 * d * kv_inner)   # q, o; k, v
            total += 2 * 2 * q_inner * (t / 2)          # scores and values
        else:
            total += 2 * d * model["num_experts"]       # the router
            total += 2 * 2 * d * model["moe_shared_expert_intermediate_size"]
            total += here * 2 * 2 * d * model["moe_intermediate_size"]
    return 3 * total
