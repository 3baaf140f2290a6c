"""Operations a looped decoder (Ouro) requires per trained token: forward
plus backward (three times the forward's matrix work), no recompute
counted. The `n_layer` shared blocks run `total_ut_steps` times and every
pass has its own head product, so both multiply. Causal attention is
counted at what causality needs (each query sees on average half the
keys). Left out, as not matrix work or under 0.1% of the total: the
embedding lookup, the exit gate's [d, 1] product, RMSNorm, the rotations,
SiLU, softmax and the exit distribution."""


def train_flops_per_unit(model, traffic):
    d, v, f = model["n_embd"], model["vocab_size"], model["intermediate_size"]
    inner = model["n_head"] * model["head_dim"]
    t = traffic["seq_len"]
    per_layer = 2 * (4 * d * inner + 3 * d * f)   # q, k, v, o; gate, up, down
    attention = 2 * 2 * inner * (t / 2)           # scores and values
    one_pass = model["n_layer"] * (per_layer + attention) + 2 * d * v
    return 3 * model["total_ut_steps"] * one_pass
