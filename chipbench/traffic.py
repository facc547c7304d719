"""The benchmark's token traffic, drawn from ``--seed``.

A copy of the motif-mixture stream of the program's
``data/pipeline.SyntheticLM``, kept here so that no change to the
program can change what the benchmark feeds it. Each row interleaves
motifs (fixed token runs, so the loss is learnable) with uniform noise
tokens at a per-row rate drawn from ``noise_rate``; the rate spreads
the per-example gradient norms the way real data of mixed quality
does. Every batch is a pure function of (seed, step): every seed gets
the same sizes, and the rows of every step differ.

A mix is a data file ``mixes/<name>.json``: the job its users run and
the job's arguments (``jobs/<job>.py`` reads them), and the rows:

  {"job": "dpsgd", "job_args": {"norms": true, "clip_norm": 1.0,
   "noise_std": 1.0, "lr": 0.0001}, "batch": 8, "seq": 1024,
   "n_motifs": 64, "motif_len": 8, "noise_rate": [0.0, 0.9]}

``batch`` is the global batch of one step.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

KEYS = ("job", "job_args", "batch", "seq", "n_motifs", "motif_len",
        "noise_rate")


def load_mix(bench_dir: Path, name: str) -> dict:
    path = Path(bench_dir) / "mixes" / f"{name}.json"
    mix = json.loads(path.read_text())
    missing = [k for k in KEYS if k not in mix]
    if missing:
        raise ValueError(f"{path}: missing {missing}")
    return mix


class MotifStream:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        base = np.random.default_rng((self.seed, 0x5EED))
        self.motifs = base.integers(0, vocab,
                                    size=(mix["n_motifs"], mix["motif_len"]))

    @property
    def tokens_per_step(self) -> int:
        return self.mix["batch"] * self.mix["seq"]

    def batch_at(self, step: int) -> dict:
        """{"ids", "labels"}: int32 numpy arrays of shape (batch, seq);
        labels are the ids shifted left by one (cyclically)."""
        b, s = self.mix["batch"], self.mix["seq"]
        lo, hi = self.mix["noise_rate"]
        rng = np.random.default_rng((self.seed, int(step), 0xDA7A))
        noise_p = rng.uniform(lo, hi, size=(b, 1))
        n_slots = s // self.mix["motif_len"] + 1
        motif_ids = rng.integers(0, self.mix["n_motifs"], size=(b, n_slots))
        seqs = self.motifs[motif_ids].reshape(b, -1)[:, :s]
        noise = rng.integers(0, self.vocab, size=(b, s))
        take_noise = rng.uniform(size=(b, s)) < noise_p
        ids = np.where(take_noise, noise, seqs).astype(np.int32)
        labels = np.roll(ids, -1, axis=1)
        labels[:, -1] = ids[:, 0]
        return {"ids": ids, "labels": labels}
