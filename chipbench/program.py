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


#: configuration-file key → the program's ``LMConfig`` field that has to
#: equal it (the first of the fields whose sub-configuration exists), so
#: that the file, which the reference reads, states what the program runs
CHECKED_FIELDS = {
    "hidden_size": ("d_model",), "num_hidden_layers": ("n_layers",),
    "vocab_size": ("vocab",), "rms_norm_eps": ("rms_eps",),
    "num_attention_heads": ("attn.n_heads",), "head_dim": ("attn.head_dim",),
    "num_key_value_heads": ("attn.n_kv",), "rope_theta": ("attn.rope_theta",),
    "attention_bias": ("attn.bias",),
    "intermediate_size": ("mlp.d_ff",),
}


def _field(obj, dotted: str):
    for part in dotted.split("."):
        obj = getattr(obj, part, None)
        if obj is None:
            return None
    return obj


def lm_config(cfg: dict):
    import_program()
    from repro.models import registry
    spec = registry.get(cfg["arch_id"])
    lm = _replace(spec.full(cfg["dtype"]), cfg.get("program", {}))
    for key, fields in CHECKED_FIELDS.items():
        if key not in cfg:
            continue
        found = [f for f in fields if _field(lm, f) is not None]
        value = _field(lm, found[0]) if found else None
        if value != cfg[key]:
            raise ValueError(f"{cfg['name']}: {key}={cfg[key]!r} in the "
                             f"file, but the program runs {fields[0]}="
                             f"{value!r}")
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
            seq: int):
    """``Trainer(loss_fn, params, PexSpec(), AdamWConfig(...),
    TrainConfig(consumers=...), DataConfig(...))``; the benchmark feeds
    it its own batches through ``run_step``."""
    import_program()
    from repro.core.taps import PexSpec
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import TrainConfig, Trainer
    return Trainer(loss_fn(cfg), params, PexSpec(),
                   AdamWConfig(**{k: job[k] for k in ADAMW_KEYS}),
                   TrainConfig(consumers=consumers(job), seed=seed),
                   DataConfig(vocab=cfg["vocab_size"], seq=seq,
                              global_batch=batch, seed=seed))

