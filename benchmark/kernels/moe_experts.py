"""What one train step's grouped expert products require: operations and
bytes from the shapes, at the EXPECTED number of rows routed to the
experts held here (`tokens x per_tok x held / experts`; a traced step's
real count is printed beside the reading by the reader). Per expert layer,
three products (gate, up, down) each run forward, for the input gradient
and for the weight gradient: 3 x 3 x 2 x rows x d x f operations. Bytes:
the held experts' weights read at the compute dtype forward and again for
the input gradient, their gradient written once in float32, and each
product's row operands and results once per pass at the compute dtype."""


def flops_and_bytes(tokens, per_tok, held, experts, layers, d, f,
                    dtype_bytes):
    rows = tokens * per_tok * held / experts
    flops = layers * 3 * 3 * 2 * rows * d * f
    weights = 3 * held * d * f
    rows_io = rows * (d + f)            # one product's operand and result
    nbytes = layers * (2 * weights * dtype_bytes + 4 * weights
                       + 3 * 3 * rows_io * dtype_bytes)
    return flops, nbytes
