"""Noise + inertia stage of a round (eqs. 4-7, the theta_max projection).

The least bytes the stage needs: read theta_L and the gradient sum in
float32 and the owner's row in the bank's type, write theta_L and the row
once: (4 + 4 + b) P + (4 + b) P, 16 P for a bf16 bank. The random bits
and theta_bar are not counted: a program that draws the bits in place or
forms theta_bar in the same pass needs no more.
"""

# Regular expressions over the trace's operation labels. The Pallas call
# carries no kernel name in the trace today; it is the one TPU custom call
# that takes (rows, 1024) random bits and returns two (rows, 1024) f32
# buffers. A kernel named "dp_round" matches too.
OPS = (r"dp_round",
       r"= \(f32\[\d+,1024\]\S*, f32\[\d+,1024\]\S*\) custom-call\("
       r".*u32\[\d+,1024\].*tpu_custom_call")


def least_bytes(n_params: int, bank_itemsize: int) -> int:
    return (12 + 2 * bank_itemsize) * n_params
