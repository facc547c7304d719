"""Plain float32 reference of the Qwen2 decoder (Yang et al. 2024,
arXiv 2407.10671; the Hugging Face ``Qwen2ForCausalLM``), written from
the published architecture:

  x = embed[ids]
  per layer:  h = RMSNorm(x);  q, k, v = h W_{q,k,v} + b_{q,k,v}
              RoPE on q, k (rotate_half, θ = rope_theta)
              causal grouped-query softmax attention, scale head_dim^-½
              x += attn W_o
              h = RMSNorm(x);  x += (SiLU(h W_gate) ⊙ h W_up) W_down
  logits = RMSNorm(x) W_head   (untied), loss = Σ_t CE(logits_t, label_t)

It also makes the benchmark's weights: ``init_params`` draws every
leaf from the seed in the configuration's dtype, and lays them out as
``{path: array}`` with the layers stacked on a leading axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import common as C


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim", d // h)
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"], hd=hd,
                f=cfg["intermediate_size"], L=cfg["num_hidden_layers"],
                V=cfg["vocab_size"])


def param_shapes(cfg: dict) -> dict:
    """path → (shape, std, dtype or None for the configuration's)."""
    n = dims(cfg)
    d, L, f, V = n["d"], n["L"], n["f"], n["V"]
    hq, hkv = n["h"] * n["hd"], n["kv"] * n["hd"]
    s = {
        "embed/table": ((V, d), 0.02, None),
        "head/w": ((d, V), 0.02, None),
        "ln_f/g": ((d,), "ones", None),
        "blocks/ln_attn/g": ((L, d), "ones", None),
        "blocks/ln_mlp/g": ((L, d), "ones", None),
        "blocks/attn/wq/w": ((L, d, hq), d ** -0.5, None),
        "blocks/attn/wk/w": ((L, d, hkv), d ** -0.5, None),
        "blocks/attn/wv/w": ((L, d, hkv), d ** -0.5, None),
        "blocks/attn/wo/w": ((L, hq, d), hq ** -0.5, None),
        "blocks/mlp/gate/w": ((L, d, f), d ** -0.5, None),
        "blocks/mlp/up/w": ((L, d, f), d ** -0.5, None),
        "blocks/mlp/down/w": ((L, f, d), f ** -0.5, None),
    }
    if cfg.get("attention_bias", True):
        for w, width in (("wq", hq), ("wk", hkv), ("wv", hkv)):
            s[f"blocks/attn/{w}/b"] = ((L, width), 0.02, None)
    return s


def init_params(cfg: dict, key) -> dict:
    return C.normal_tree(key, param_shapes(cfg), jnp.dtype(cfg["dtype"]))


def example_loss(p: dict, ids, labels, mode: str = "f32", *, cfg: dict):
    n = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    s = ids.shape[0]
    x = p["embed/table"][ids].astype(C.F32)
    for layer in range(n["L"]):
        w = {k[len("blocks/"):]: v[layer] for k, v in p.items()
             if k.startswith("blocks/")}
        h = C.rmsnorm(x, w["ln_attn/g"], eps)

        def proj(name, heads):
            y = C.einsum("sd,de->se", h, w[f"attn/{name}/w"], mode)
            if f"attn/{name}/b" in w:
                y = y + w[f"attn/{name}/b"]
            return y.reshape(s, heads, n["hd"])

        q = C.rope(proj("wq", n["h"]), theta)
        k = C.rope(proj("wk", n["kv"]), theta)
        v = proj("wv", n["kv"])
        a = C.causal_gqa(q, k, v, mode)
        x = x + C.einsum("se,ed->sd", a, w["attn/wo/w"], mode)
        h = C.rmsnorm(x, w["ln_mlp/g"], eps)
        g = C.einsum("sd,df->sf", h, w["mlp/gate/w"], mode)
        u = C.einsum("sd,df->sf", h, w["mlp/up/w"], mode)
        x = x + C.einsum("sf,fd->sd", jax.nn.silu(g) * u, w["mlp/down/w"],
                         mode)
    x = C.rmsnorm(x, p["ln_f/g"], eps)
    logits = C.einsum("sd,dv->sv", x, p["head/w"], mode)
    return C.token_xent_sum(logits, labels)

