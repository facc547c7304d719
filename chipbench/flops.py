"""Operations a step requires per token, from a configuration's shapes.

The benchmark's own count, from the published keys of the
configuration file (Hugging Face names), for decoder transformers with
dense gated feed-forward layers:

  * matrix parameters a token goes through: Q, K, V, O projections,
    the three feed-forward matrices, and the output head. The
    embedding lookup is a gather and is not counted;
  * each such parameter costs 2 operations per token forward, 2 more
    for the activation gradient and 2 more for the weight gradient;
  * attention: QKᵀ and AV cost 2·ctx·head_dim operations each per head
    and token forward, ctx being the causal context a query sees,
    (seq + 1)/2 on average: only the lower triangle is required work.
    Its backward costs twice its forward, and is required whether or
    not weight gradients are: attention has no weights.

Recomputation (rematerialisation) and the per-example statistics are
not counted, so a utilisation built on this count reads the same
whichever method computes the norms, and cannot pass 100%.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Matrix parameters one token goes through."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // h)
    kv = cfg["num_key_value_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    ffn = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + ffn) + d * cfg["vocab_size"]


def attention_forward(cfg: dict, seq: int) -> float:
    """QKᵀ and AV operations per token, forward, all layers."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // h)
    ctx = (seq + 1) / 2
    return cfg["num_hidden_layers"] * 2 * (2 * ctx * hd) * h


def per_token(cfg: dict, seq: int, weight_grads: bool) -> float:
    """Required operations per token: forward and activation backward,
    plus the weight backward where ``weight_grads``."""
    passes = 6 if weight_grads else 4
    return passes * matmul_params(cfg) + 3 * attention_forward(cfg, seq)
