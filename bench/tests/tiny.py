"""Cells at a size a CPU test run holds, for the harness's own tests.

Same families and code paths as the benchmark's configurations, at widths
of 64.  The limits here are the tiny cells' own, about three times the
largest gap sound runs gave on the CPU over four seeds; the benchmark's
cells have theirs in `bench/limits/`.
"""
from __future__ import annotations

DENSE = {"name": "tiny-dense", "reference": "lm", "family": "dense",
         "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256, "attention": "full",
         "window": 4096, "qkv_bias": True, "tie_embeddings": True,
         "rope_theta": 1e6, "norm_eps": 1e-6, "mlp_kind": "swiglu",
         "dtype": "bfloat16"}
HYBRID = dict(DENSE, name="tiny-hybrid", family="hybrid", qkv_bias=False,
              tie_embeddings=False, attention="swa", window=32, ssm_state=8,
              ssm_expand=2, rope_theta=1e4, norm_eps=1e-5)

TRAFFIC = {
    "tiny-dense": {"kind": "train", "batch": 4, "seq_len": 64},
    "tiny-hybrid": {"kind": "train", "batch": 1, "seq_len": 256},
}

LIMITS = {
    "tiny-dense": {"loss_gap": 1.2e-3, "grad_norm_gap": 1.2e-3,
                   "grad_leaf_gap": 2.7e-2, "update_leaf_gap": 6e-2},
    "tiny-hybrid": {"loss_gap": 4e-5, "grad_norm_gap": 5e-4,
                    "grad_leaf_gap": 4.2e-3, "update_leaf_gap": 2.2e-3},
}

END_TO_END = [
    {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.01, "source": "host_clock"},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
     "source": "host_clock"},
]
PER_LAYER = [
    {"name": "idle_share.train", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "device",
     "moves": "train_tokens_per_s"},
    {"name": "mfu.train", "unit": "%", "better": "higher",
     "source": "host_clock", "layer": "model step",
     "moves": "train_tokens_per_s"},
]


def spec(config: dict) -> dict:
    """What `bench.run.cell_spec` returns, for a tiny cell."""
    name = config["name"]
    return {"cell": {"name": name + ".train", "config": name,
                     "traffic": "tiny", "chips": 1},
            "config": config, "traffic": TRAFFIC[name],
            "limits": LIMITS[name], "end_to_end": END_TO_END,
            "per_layer": PER_LAYER}
