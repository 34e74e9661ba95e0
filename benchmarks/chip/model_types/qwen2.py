"""Qwen2 (arXiv:2407.10671), as the program runs it: the configuration
file's keys by the program's ``ModelConfig`` fields, and the model FLOPs
of a token's forward pass. The plain reference is ``reference/qwen2.py``.

FLOPs (d hidden, h query heads, k key/value heads of size e, f
feed-forward, L layers, V vocabulary), forward per token at sequence
length S, a multiply-add being 2:
  projections  q: 2 d h e;  k and v: 2 * 2 d k e;  o: 2 h e d
  MLP          gate, up, down: 3 * 2 d f
  attention    scores and values, causal: a token attends to (S+1)/2 keys
               on average, 2 * 2 h e (S+1)/2
  LM head      2 d V (tied to the embedding; counted, as it is a matmul)
"""

FIELDS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size",
          "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
          "tie_word_embeddings": "tie_embeddings"}
DEPTH = "num_hidden_layers"
SET = {"rope_theta": "rope_theta"}


def check(cfg):
    if not cfg.qkv_bias:
        raise ValueError(f"{cfg.name}: qwen2 has q/k/v biases")


def forward_flops(cfg: dict, S: int) -> float:
    d = cfg["hidden_size"]
    h, k = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e = d // h
    f, L, V = cfg["intermediate_size"], cfg["num_hidden_layers"], \
        cfg["vocab_size"]
    proj = 2 * d * h * e + 2 * 2 * d * k * e + 2 * h * e * d
    mlp = 3 * 2 * d * f
    attn = 2 * 2 * h * e * (S + 1) / 2
    return L * (proj + mlp + attn) + 2 * d * V
