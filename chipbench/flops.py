"""Operations a step requires per token, from a configuration's shapes.

The benchmark's own count, from the published keys of the
configuration file (Hugging Face names), for decoder transformers, by
the kind of each block:

  * attention: grouped-query (Q, K, V, O projections) or, where the
    file has ``kv_lora_rank``, latent attention: Q through
    ``q_lora_rank`` where it is set (else one projection), ``kv_down``
    to ``kv_lora_rank + qk_rope_head_dim``, ``kv_up`` to
    ``heads × (qk_nope_head_dim + v_head_dim)``, and O;
  * feed-forward: dense gated (three matrices of ``intermediate_size``)
    in every layer of a model without experts, and in the first
    ``first_k_dense_replace`` layers of one with them. An expert layer
    counts its router at the published expert count, its shared
    experts (``n_shared_experts`` × three matrices of the expert width,
    ``moe_intermediate_size`` or else ``intermediate_size``), and
    ``top_k × held / published`` routed experts: the expected share of
    a token's experts that lie on this chip when the routing is even,
    ``held`` being the file's expert count and ``published`` the
    published one (``reduced``);
  * the output head. The embedding lookup is a gather and is not
    counted.

Each matrix parameter costs 2 operations per token forward, 2 more for
the activation gradient and 2 more for the weight gradient. Attention:
QKᵀ costs 2·ctx·(query-key width) and AV 2·ctx·(value width)
operations per head and token forward, ctx being the causal context a
query sees, (seq + 1)/2 on average: only the lower triangle is required
work. Its backward costs twice its forward, and is required whether or
not weight gradients are: attention has no weights.

Recomputation (rematerialisation) and the per-example statistics are
not counted, so a utilisation built on this count reads the same
whichever method computes the norms, and cannot pass 100%.
"""
from __future__ import annotations

EXPERT_KEYS = ("n_routed_experts", "num_local_experts")


def _heads(cfg: dict) -> tuple:
    """(heads, query-key width, value width) of one attention head."""
    h = cfg["num_attention_heads"]
    if "kv_lora_rank" in cfg:
        qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        return h, qk, cfg["v_head_dim"]
    hd = cfg.get("head_dim", cfg["hidden_size"] // h)
    return h, hd, hd


def attention_params(cfg: dict) -> int:
    """Matrix parameters of one attention block."""
    d = cfg["hidden_size"]
    h, qk, v = _heads(cfg)
    if "kv_lora_rank" in cfg:
        q_lora = cfg.get("q_lora_rank")
        q = d * q_lora + q_lora * h * qk if q_lora else d * h * qk
        kv = d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"] + v)
        return q + kv + h * v * d
    kv = cfg["num_key_value_heads"]
    return d * h * qk + 2 * d * kv * qk + h * qk * d


def experts(cfg: dict):
    """(held, published) routed experts of an expert layer, or None."""
    for key in EXPERT_KEYS:
        if key in cfg:
            cut = cfg.get("reduced", {}).get(key)
            return cfg[key], cut["published"] if cut else cfg[key]
    return None


def ffn_params(cfg: dict) -> int:
    """Matrix parameters a token goes through in the feed-forward
    blocks of all layers."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = 3 * d * cfg["intermediate_size"]
    if experts(cfg) is None:
        return layers * dense
    held, published = experts(cfg)
    n_dense = cfg.get("first_k_dense_replace", 0)
    f = cfg.get("moe_intermediate_size", cfg["intermediate_size"])
    expert = 3 * d * f
    moe = d * published + cfg.get("n_shared_experts", 0) * expert \
        + cfg["num_experts_per_tok"] * held * expert / published
    return n_dense * dense + (layers - n_dense) * moe


def matmul_params(cfg: dict) -> int:
    """Matrix parameters one token goes through."""
    return cfg["num_hidden_layers"] * attention_params(cfg) \
        + ffn_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]


def attention_forward(cfg: dict, seq: int) -> float:
    """QKᵀ and AV operations per token, forward, all layers."""
    h, qk, v = _heads(cfg)
    ctx = (seq + 1) / 2
    return cfg["num_hidden_layers"] * (2 * ctx * qk + 2 * ctx * v) * h


def per_token(cfg: dict, seq: int, weight_grads: bool) -> float:
    """Required operations per token: forward and activation backward,
    plus the weight backward where ``weight_grads``."""
    passes = 6 if weight_grads else 4
    return passes * matmul_params(cfg) + 3 * attention_forward(cfg, seq)
