"""Per-record clipping: the squared norm of one record's float32
gradient, read once: 4 P bytes per record."""

# The sqnorm Pallas call: one (rows, 1024) f32 operand, one (8, 128) tile
# of partial sums per block. It carries no kernel name in the trace today;
# a kernel named "sqnorm" matches too.
OPS = (r"sqnorm",
       r"= f32\[\d+,128\]\S* custom-call\(f32\[\d+,1024\]\S* "
       r"%[\w.\-]+\), custom_call_target=\"tpu_custom_call\"")


def least_bytes(n_params: int) -> int:
    return 4 * n_params
