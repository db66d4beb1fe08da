"""Model FLOPs of a training step: the benchmark's own copy of the program's
arithmetic (6 N D, with N the parameters a token activates), so that no
change to the program moves the yardstick of `mfu`.

`cfg` is a configuration file of `bench/configs/`.  The copy covers the
block kinds of those configurations, dense attention and hybrid
(attention beside an SSM head); a configuration of another family brings
its branch with it.
"""
from __future__ import annotations


def param_count(cfg: dict) -> float:
    if cfg["family"] not in ("dense", "hybrid"):
        raise ValueError(f"no FLOP arithmetic for family {cfg['family']!r}")
    d = cfg["d_model"]
    hd = cfg.get("head_dim") or d // cfg["n_heads"]
    h, kv, vocab = cfg["n_heads"], cfg["n_kv_heads"], cfg["vocab_size"]
    n_mats = 3 if cfg.get("mlp_kind", "swiglu") == "swiglu" else 2
    per_layer = 2 * d + 2 * d * h * hd + 2 * d * kv * hd
    if cfg["family"] == "hybrid":
        d_in = cfg["ssm_expand"] * d
        per_layer += 3 * d * d_in + d_in * 2 * cfg["ssm_state"] + d_in
    if cfg["d_ff"] > 0:
        per_layer += n_mats * d * cfg["d_ff"]
    embed = vocab * d if cfg["tie_embeddings"] else 2 * vocab * d
    return float(embed + cfg["n_layers"] * per_layer)


def train_flops_per_token(cfg: dict) -> float:
    """Forward and backward FLOPs per trained token; recomputation excluded."""
    return 6.0 * param_count(cfg)
