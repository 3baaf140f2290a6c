"""Operations a GPT-2 shaped decoder requires per trained token: forward
plus backward (three times the forward's matrix work), no recompute.
Causal attention is counted at what causality needs (each query sees on
average half the keys). Embedding lookups, LayerNorm, GELU and softmax are
left out: they are not matrix work and are under 1% of the total."""


def train_flops_per_unit(model, traffic):
    d, n_layer, v = model["n_embd"], model["n_layer"], model["vocab_size"]
    t = traffic["seq_len"]
    per_layer = 2 * (3 * d * d + d * d + 8 * d * d)  # qkv, out, mlp
    attention = 2 * 2 * d * (t / 2)                  # scores and values
    forward = n_layer * (per_layer + attention) + 2 * d * v
    return 3 * forward
