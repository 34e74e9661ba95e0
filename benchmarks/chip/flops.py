"""Model FLOPs of a training token, from a configuration file's shapes.

A multiply-add is 2 FLOPs. Training counts the forward pass and a backward
pass of twice its work, 3x the forward FLOPs, for every matmul-form term.
Not counted: recomputation (remat), the optimizer's elementwise work,
norms, activations, softmax and other elementwise operations, and the
embedding gather. Every term is per token, at sequence length S.

Qwen2 (d hidden, h query heads, k key/value heads of size e, f feed-forward,
L layers, V vocabulary), forward per token:
  projections  q: 2 d h e;  k and v: 2 * 2 d k e;  o: 2 h e d
  MLP          gate, up, down: 3 * 2 d f
  attention    scores and values, causal: a token attends to (S+1)/2 keys
               on average, 2 * 2 h e (S+1)/2
  LM head      2 d V (tied to the embedding; counted, as it is a matmul)

Mamba-2 (d model, d_in = expand d, H = d_in / P heads of size P, N state,
G groups, chunk Q, L layers, V vocabulary), forward per token:
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
from __future__ import annotations


def qwen2_forward(cfg: dict, S: int) -> float:
    d = cfg["hidden_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e = d // h
    f, L, V = cfg["intermediate_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]
    proj = 2 * d * h * e + 2 * 2 * d * k * e + 2 * h * e * d
    mlp = 3 * 2 * d * f
    attn = 2 * 2 * h * e * (S + 1) / 2
    return L * (proj + mlp + attn) + 2 * d * V


def mamba2_forward(cfg: dict, S: int) -> float:
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


FORWARD = {"qwen2": qwen2_forward, "mamba2": mamba2_forward}


def train_flops_per_token(cfg: dict, S: int) -> float:
    return 3.0 * FORWARD[cfg["model_type"]](cfg, S)
