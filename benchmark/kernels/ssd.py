"""What one train step's Mamba-2 recurrence requires in its chunkwise form
(the recurrence alone, the program's scope `ssd_core`): operations and
bytes from the shapes. A chunk of `chunk` positions: `C B^T` `[Q, N] x [N,
Q]` once a GROUP; a head the masked product `[Q, Q] x [Q, P]`, the chunk's
closing state `[P, Q] x [Q, N]` and the carried state's part `[Q, N] x [N,
P]`; and the states the chunks start from, each the decayed sum of the
closing states before it (`tokens / chunk` states of `[P, N]` a head, a
product with the `[chunks, chunks]` matrix of decays). The local product
is counted whole, as the form computes it, not at the half causality
needs. The backward pass is twice the forward's products. Bytes, float32
(`dtype_bytes`): x, dt, B, C read and y written once forward; read again
with y's gradient, and their gradients written once, backward; the
float32 state of every chunk boundary written forward and read backward.
Not counted: the decays' exponentials and the running sums, which are not
matrix work. The count reads the same work whatever implements the
scope."""


def forward_flops_per_token(heads, head_dim, groups, state, chunk, seq_len):
    """Operations a token of the chunkwise form's forward pass, all heads
    of one layer."""
    q = min(chunk, seq_len)
    chunks = seq_len / q
    a_head = (2 * q * head_dim                  # (C B^T o L) (dt x)
              + 2 * 2 * head_dim * state        # closing state; carried part
              + 2 * chunks * head_dim * state / q)   # states before chunks
    return groups * 2 * q * state + heads * a_head


def flops_and_bytes(tokens, seq_len, heads, head_dim, groups, state, chunk,
                    layers, dtype_bytes):
    flops = layers * 3 * tokens * forward_flops_per_token(
        heads, head_dim, groups, state, chunk, seq_len)
    read = tokens * (heads * head_dim + heads + 2 * groups * state)
    y = tokens * heads * head_dim
    states = tokens / min(chunk, seq_len) * heads * head_dim * state
    nbytes = layers * ((3 * read + 2 * y) * dtype_bytes + 2 * states * 4)
    return flops, nbytes
