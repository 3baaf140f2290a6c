"""What a step's flash-attention forward calls under the block-diffusion
mask require (the program's scope `flash_attn.fwd/flash_attn_fwd/
flash_attn_bd_fwd`): operations and bytes from the shapes. A sequence of
`seq_len` = T tokens in blocks of `block_len` = L is 2T positions, and of
the (2T)^2 score pairs these are live: the clean copy's queries on the
clean keys of their own block and of those before it, T (T + L) / 2; the
noised copy's on the clean keys of the blocks before theirs, T (T - L) /
2, and on the noised keys of their own block, T L: T^2 + T L in all, the
work of two causal attentions of length T and not of one of length 2T.
Each live pair costs a score product and a value product over the head's
width. Bytes: q, k and v read once at `in_bytes` a number (the kernels
read them rounded, PR 47) and the output written once at `out_bytes`, all
2T positions of them, one call a layer a step."""


def live_pairs(seq_len, block_len):
    """Score pairs a sequence's 2 `seq_len` positions need."""
    return seq_len * seq_len + seq_len * block_len


def flops_and_bytes(batch, heads, positions, block_len, head_dim, layers,
                    in_bytes, out_bytes):
    """`positions` = 2T: what a sequence takes through the layers (the
    configuration's `model.seq_len`)."""
    flops = (layers * batch * heads * 2 * 2 * head_dim
             * live_pairs(positions // 2, block_len))
    nbytes = (layers * batch * heads * positions * head_dim
              * (3 * in_bytes + out_bytes))
    return flops, nbytes
