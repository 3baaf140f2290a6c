"""What one causal flash-attention forward call requires: operations and
bytes from its shapes. Causality halves the score and value products;
bytes are q, k, v read and the output written once, at the compute
dtype's width."""


def flops_and_bytes(batch, heads, seq_len, width, dtype_bytes):
    head_dim = width // heads
    flops = 2 * 2 * batch * heads * seq_len * seq_len * head_dim / 2
    nbytes = 4 * batch * heads * seq_len * head_dim * dtype_bytes
    return flops, nbytes
