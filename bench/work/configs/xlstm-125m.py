"""Operations one round of xlstm-125m needs.

6 x the matrix parameters (every weight that multiplies an activation:
projections, gate and recurrent matrices, the depthwise conv and the
unembedding; not the embedding table, which is a lookup, nor norm gains
and biases) x tokens, plus the mLSTM's own recurrence by its published
equations, forward and backward (3 x forward). Nothing is counted for
recomputation or for the privacy arithmetic.
"""


def matrix_params(m: dict) -> int:
    d, V, H = m["d_model"], m["vocab"], m["n_heads"]
    x = m["xlstm"]
    dm = int(d * x.get("mlstm_proj_factor", 2.0))
    K = x.get("conv_kernel", 4)
    fs = int(d * x.get("slstm_proj_factor", 4.0 / 3.0))
    hd = d // H
    mlstm = 2 * d * dm + K * dm + 3 * dm * dm + 2 * dm * H + dm * d
    slstm = d * H * hd * 4 + H * hd * hd * 4 + d * 2 * fs + fs * d
    n_s = len(x.get("slstm_indices", ()))
    return (m["n_layers"] - n_s) * mlstm + n_s * slstm + d * V


def mixer_flops_per_token(m: dict, seq: int) -> int:
    """mLSTM, per head and token: C = f C + i k v^T (3 N^2), n = f n + i k
    (3 N), read-out q^T C and q.n (2 N^2 + 2 N); x3 for the backward."""
    d, H = m["d_model"], m["n_heads"]
    x = m["xlstm"]
    N = int(d * x.get("mlstm_proj_factor", 2.0)) // H
    n_m = m["n_layers"] - len(x.get("slstm_indices", ()))
    return 3 * n_m * H * (5 * N * N + 5 * N)


def flops_per_round(m: dict, seq: int, batch: int) -> float:
    tokens = seq * batch
    return float(tokens * (6 * matrix_params(m)
                           + mixer_flops_per_token(m, seq)))
