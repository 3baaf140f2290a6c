"""What one train step's grouped expert products require where the experts
are UNGATED (`E(u) = act(u W_up) W_down`: the Nemotron-H family's): as
`kernels/moe_experts.py`, with two products a layer and not three.
Operations and bytes from the shapes, at the EXPECTED number of rows
routed to the experts held here (`tokens x per_tok x held / experts`; a
traced step's real count is printed beside the reading by the reader).
Per expert layer, two products (up, down) each run forward, for the input
gradient and for the weight gradient: 2 x 3 x 2 x rows x d x f operations.
Bytes: the held experts' weights read at the compute dtype forward and
again for the input gradient, their gradient written once in float32, and
each product's row operands and results once per pass at the compute
dtype."""


def flops_and_bytes(tokens, per_tok, held, experts, layers, d, f,
                    dtype_bytes):
    rows = tokens * per_tok * held / experts
    flops = layers * 2 * 3 * 2 * rows * d * f
    weights = 2 * held * d * f
    rows_io = rows * (d + f)            # one product's operand and result
    nbytes = layers * (2 * weights * dtype_bytes + 4 * weights
                       + 2 * 3 * rows_io * dtype_bytes)
    return flops, nbytes
