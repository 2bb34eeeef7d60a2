"""A cell small enough for the CPU: danube's block at toy widths."""
from __future__ import annotations

from chipbench.spec import Cell

CONFIG = {
    "arch": "h2o-danube-1.8b",
    "overrides": {"n_layers": 2, "d_model": 256, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 64, "d_ff": 512,
                  "vocab": 512},
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 64, "intermediate_size": 512, "vocab_size": 512,
    "num_hidden_layers": 2, "sliding_window": 4096, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
}

OPT = {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1, "clip_norm": 1.0, "schedule": "constant",
       "warmup": 100}
FLAGS = ["--optimizer", "adamw", "--lr", "3e-4", "--schedule", "constant",
         "--warmup", "100"]


def cell(dp: int = 1, sync: str = "fp", limits: dict | None = None,
         seq_len: int = 64, batch: int = 4) -> Cell:
    traffic = {"dp": dp, "tp": 1, "seq_len": seq_len, "global_batch": batch,
               "ring": 4, "microbatch": 1, "flags": FLAGS + ["--sync", sync],
               "optimizer": OPT, "sync": {"strategy": sync}}
    return Cell(name=f"tiny.{sync}.dp{dp}", chips=dp, config=CONFIG,
                traffic=traffic, limits=limits or {})
