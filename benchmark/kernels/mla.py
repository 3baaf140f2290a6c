"""What one train step's multi-head latent attention mixers require (the
program's scope `mla`: the mixer whole), operations and bytes from the
shapes. A layer's forward, a token: the five projections (`W_qa` [d,
q_rank], `W_qb` [q_rank, H (nope + rope)], `W_kva` [d, kv_rank + rope],
`W_kvb` [kv_rank, H (nope + v)], `W_o` [H v, d]) and the causal scores
and values at what causality needs (each query sees on average half the
keys: `2 H (nope + rope) T/2` and `2 H v T/2`), in the training form: the
up-projections are not folded into the query (the absorbed form would
widen the scores to `kv_rank`). Forward plus backward is three times the
forward's products; no recompute is counted (the flash backward's second
pass over the scores is the kernel's own business). Bytes: the weights
read at the compute dtype forward and again for the input gradient, their
gradient written once in float32; each projection's operand and result
once a pass (three passes) at the compute dtype; q, k, v read and o
written forward, and q, k, v, o and o's gradient read and three gradients
written backward. Not counted: the two RMSNorms, the rotations and the
broadcast of the shared rotary key, which are not matrix work.

With `flash_forward_only` the count is the forward kernel's calls alone
(one a layer at `[heads, seq_len, nope + rope]`, as
`kernels/flash_attn.py` counts one), for the scope
`flash_attn.fwd/flash_attn_fwd`."""


def projection_params(d, heads, q_rank, kv_rank, nope, rope, v):
    return (d * q_rank + q_rank * heads * (nope + rope)
            + d * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + heads * v * d)


def forward_flops_per_token(seq_len, d, heads, q_rank, kv_rank, nope, rope,
                            v):
    """(the projections', the causal scores' and values') operations a
    token of one layer's forward pass."""
    scores = 2 * heads * (nope + rope) * seq_len / 2
    values = 2 * heads * v * seq_len / 2
    return (2 * projection_params(d, heads, q_rank, kv_rank, nope, rope, v),
            scores + values)


def flops_and_bytes(tokens, seq_len, d, heads, q_rank, kv_rank, nope, rope,
                    v, layers, dtype_bytes, flash_forward_only=0):
    proj, attn = forward_flops_per_token(seq_len, d, heads, q_rank, kv_rank,
                                         nope, rope, v)
    qk = heads * (nope + rope)
    if flash_forward_only:
        return (layers * tokens * attn,
                layers * tokens * (3 * qk + heads * v) * dtype_bytes)
    flops = layers * 3 * tokens * (proj + attn)
    weights = projection_params(d, heads, q_rank, kv_rank, nope, rope, v)
    rows_io = tokens * ((d + q_rank) + (q_rank + qk) + (d + kv_rank + rope)
                        + (kv_rank + heads * (nope + v)) + (heads * v + d))
    attn_io = tokens * (4 + 8) * qk
    nbytes = layers * (2 * weights * dtype_bytes + 4 * weights
                       + 3 * rows_io * dtype_bytes + attn_io * dtype_bytes)
    return flops, nbytes
