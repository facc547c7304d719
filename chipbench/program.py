"""The system under test, as the benchmark drives it.

This is the only module of the benchmark that imports the program
(``repro``, from ``src/`` of the same checkout), and it uses only its
public entry points with their default choices: the registry's model
configuration and loss, and ``Trainer`` with ``PexSpec()``. It passes
no ``use_pallas``, ``seg_method`` or ``method``.

A configuration file names the registry ``arch_id`` and, under
``program``, the fields of the registry's full configuration that the
benchmark changes, dotted for nested ones (``"attn.head_multiple"``).
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

from chipbench.flops import EXPERT_KEYS, experts

REPO = Path(__file__).resolve().parents[1]


def import_program():
    src = REPO / "src"
    if not (src / "repro").is_dir():
        raise ImportError(f"the program is not in this checkout ({src})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro
    if src not in Path(repro.__file__).resolve().parents:
        raise ImportError(f"repro imported from {repro.__file__}, not "
                          f"from {src}")
    return repro


def _replace(obj, dotted: dict):
    flat = {k: v for k, v in dotted.items() if "." not in k}
    nested: dict = {}
    for k, v in dotted.items():
        if "." in k:
            head, rest = k.split(".", 1)
            nested.setdefault(head, {})[rest] = v
    for head, sub in nested.items():
        flat[head] = _replace(getattr(obj, head), sub)
    return dataclasses.replace(obj, **flat)


#: configuration-file key → the program's ``LMConfig`` fields that may
#: hold it, in order: the first whose sub-configuration exists (``attn``
#: or ``mla``; ``mlp``, ``dense_prefix_mlp`` or ``moe``) has to equal
#: the file's value, so that the file, which the reference reads,
#: states what the program runs. A key the file does not have is not
#: checked; a value may be null where the field is (``q_lora_rank``),
#: and false where no such sub-configuration exists (``attention_bias``
#: of a latent-attention model).
CHECKED_FIELDS = {
    "hidden_size": ("d_model",), "num_hidden_layers": ("n_layers",),
    "vocab_size": ("vocab",), "rms_norm_eps": ("rms_eps",),
    "num_attention_heads": ("attn.n_heads", "mla.n_heads"),
    "head_dim": ("attn.head_dim",),
    "num_key_value_heads": ("attn.n_kv", "mla.n_heads"),
    "rope_theta": ("attn.rope_theta", "mla.rope_theta"),
    "attention_bias": ("attn.bias",),
    # phi3.5-moe names its expert width ``intermediate_size``
    "intermediate_size": ("mlp.d_ff", "dense_prefix_mlp.d_ff", "moe.d_ff"),
    "kv_lora_rank": ("mla.kv_lora",), "q_lora_rank": ("mla.q_lora",),
    "qk_nope_head_dim": ("mla.qk_nope",), "qk_rope_head_dim": ("mla.qk_rope",),
    "v_head_dim": ("mla.v_dim",),
    "moe_intermediate_size": ("moe.d_ff",),
    "n_shared_experts": ("moe.n_shared",),
    "num_experts_per_tok": ("moe.top_k",),
    "first_k_dense_replace": ("n_dense_prefix",),
    "routed_scaling_factor": ("moe.routed_scale",),
    "norm_topk_prob": ("moe.renorm_topk",),
}

#: ``flops.EXPERT_KEYS`` count a layer's routed experts. The file gives
#: the number held on this chip (its share of an expert-parallel
#: deployment); the router's width, ``moe.n_experts``, is the published
#: count, which ``reduced[key]["published"]`` states where the two
#: differ. The held count has no field of the program yet: the cell's
#: ``correct`` guards it, since a program that computed the absent
#: experts too would leave the reference, which is given the same
#: share, by their whole part.
EXPERT_FIELD = ("moe.n_experts",)


def _field(obj, dotted: str):
    """(whether the field's sub-configuration exists, its value)."""
    *head, last = dotted.split(".")
    for part in head:
        obj = getattr(obj, part, None)
        if obj is None:
            return False, None
    return True, getattr(obj, last, None)


def _expected(cfg: dict) -> dict:
    """The file's checked keys and the value the program has to hold."""
    out = {key: cfg[key] for key in CHECKED_FIELDS if key in cfg}
    out.update({key: experts(cfg)[1] for key in EXPERT_KEYS if key in cfg})
    return out


def lm_config(cfg: dict):
    import_program()
    from repro.models import registry
    spec = registry.get(cfg["arch_id"])
    lm = _replace(spec.full(cfg["dtype"]), cfg.get("program", {}))
    fields = dict(CHECKED_FIELDS, **dict.fromkeys(EXPERT_KEYS, EXPERT_FIELD))
    for key, want in _expected(cfg).items():
        found = [v for ok, v in (_field(lm, f) for f in fields[key]) if ok]
        value = found[0] if found else None
        # a false flag (``attention_bias``) holds of a block without it
        if value != want and not (not found and want is False):
            raise ValueError(f"{cfg['name']}: {key}={want!r} in the "
                             f"file, but the program runs "
                             f"{'/'.join(fields[key])}={value!r}")
    return spec, lm


def loss_fn(cfg: dict):
    from repro.models import registry
    spec, lm = lm_config(cfg)
    return registry.make_loss_fn_v2(spec, lm)


def consumers(job: dict):
    """The consumer list of a training job:
    ``{"norms": true, "clip_norm": 1.0, "noise_std": 1.0}`` or
    ``{"grads": true}``."""
    from repro.core import plan as P
    out = []
    if job.get("norms"):
        out.append(P.Norms())
    if job.get("grads"):
        out.append(P.Grads())
    if job.get("clip_norm") is not None:
        out.append(P.Clip(job["clip_norm"]))
    if job.get("noise_std"):
        out.append(P.Noise(job["noise_std"]))
    return tuple(out)


#: the AdamW settings a training mix states (``AdamWConfig`` fields)
ADAMW_KEYS = ("lr", "b1", "b2", "eps", "weight_decay", "global_clip")


def trainer(cfg: dict, params, job: dict, *, seed: int, batch: int,
            seq: int, mesh=None):
    """``Trainer(loss_fn, params, PexSpec(), AdamWConfig(...),
    TrainConfig(consumers=...), DataConfig(...), mesh=mesh)``; the
    benchmark feeds it its own batches through ``run_step``. A mesh
    (data axis ``"data"``) runs the data-parallel step: each shard's
    clipped sum, one psum of the gradients, the noise once after it."""
    import_program()
    from repro.core.taps import PexSpec
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import TrainConfig, Trainer
    return Trainer(loss_fn(cfg), params, PexSpec(),
                   AdamWConfig(**{k: job[k] for k in ADAMW_KEYS}),
                   TrainConfig(consumers=consumers(job), seed=seed),
                   DataConfig(vocab=cfg["vocab_size"], seq=seq,
                              global_batch=batch, seed=seed),
                   mesh=mesh, data_axes=("data",))
