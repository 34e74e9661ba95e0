"""Mamba-2 (arXiv:2405.21060), as the program runs it: the configuration
file's keys by the program's ``ModelConfig`` fields, and the model FLOPs
of a token's forward pass. The plain reference is ``reference/mamba2.py``.

FLOPs (d model, d_in = expand d, H = d_in / P heads of size P, N state,
G groups, chunk Q, L layers, V vocabulary), forward per token at sequence
length S, a multiply-add being 2:
  projections  x, z: 2 * 2 d d_in;  B, C: 2 * 2 d G N;  dt: 2 d H;
               out: 2 d_in d
  SSD scan, per head, in its matmul form (chunk Q = min(chunk_size, S)):
    C B^T within the chunk, causal:         2 N (Q+1)/2
    (C B^T . decay) x within the chunk:     2 P (Q+1)/2
    chunk states  B^T (decay x):            2 N P
    states into outputs  C h:               2 N P
    state passing between chunks:           2 N P / Q
  LM head      2 d V (untied)
The convolution (width d_conv, depthwise) is elementwise and not counted.
"""

FIELDS = {"d_model": "d_model", "vocab_size": "vocab_size",
          "norm_epsilon": "norm_eps", "tie_embeddings": "tie_embeddings",
          "d_state": "ssm.d_state", "d_conv": "ssm.d_conv",
          "expand": "ssm.expand", "headdim": "ssm.head_dim",
          "ngroups": "ssm.ngroups", "chunk_size": "ssm.chunk"}
DEPTH = "n_layer"
SET = {"norm_epsilon": "norm_eps", "vocab_size": "vocab_size"}


def forward_flops(cfg: dict, S: int) -> float:
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    P, N, G = cfg["headdim"], cfg["d_state"], cfg["ngroups"]
    H = d_in // P
    Q = min(cfg["chunk_size"], S)
    L, V = cfg["n_layer"], cfg["vocab_size"]
    proj = 2 * 2 * d * d_in + 2 * 2 * d * G * N + 2 * d * H + 2 * d_in * d
    ssd = H * (2 * N * (Q + 1) / 2 + 2 * P * (Q + 1) / 2 + 2 * N * P
               + 2 * N * P + 2 * N * P / Q)
    return L * (proj + ssd) + 2 * d * V
