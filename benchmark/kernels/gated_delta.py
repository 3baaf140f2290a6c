"""What one train step's gated delta rule requires in its chunkwise form
(the recurrence alone, the program's scope `gdn_core`): operations and
bytes from the shapes. A chunk of `chunk` positions of one value head:
the key-key and query-key products `[C, dk] x [dk, C]` (done once a KEY
head, so at `k_heads / v_heads` a value head), the unit lower triangular
system `(I + A) [U | W] = [beta v | beta exp(G) k]` at what forward
substitution needs (`C^2 (dk + dv)`; the program's product form of the
inverse spends more and is not what the rule requires), and in the scan
over chunk states `W S`, `q S` and `k^T v'` (`2 C dk dv` each) and the
chunk-local `[C, C] x [C, dv]`. The backward pass is twice the forward's
products. Bytes: q, k, v, g, beta read and o written once forward, read
again with o's gradient and their gradients written once backward, at
`dtype_bytes`; the float32 state of every chunk boundary written forward
and read backward."""


def flops_and_bytes(tokens, k_heads, v_heads, dk, dv, chunk, layers,
                    dtype_bytes):
    chunks = tokens / chunk
    shared = 2 * 2 * chunk * chunk * dk * k_heads / v_heads
    solve = chunk * chunk * (dk + dv)
    scan = 3 * 2 * chunk * dk * dv + 2 * chunk * chunk * dv
    flops = layers * 3 * chunks * v_heads * (shared + solve + scan)
    io = tokens * (2 * k_heads * dk + 2 * v_heads * dv + 2 * v_heads)
    states = chunks * v_heads * dk * dv
    nbytes = layers * (2 * io * dtype_bytes + 2 * states * 4)
    return flops, nbytes
