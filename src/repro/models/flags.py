"""Model-level optimization flags (the §Perf hillclimb levers).

Explicit global switches so the dry-run can lower baseline and optimized
variants of the same architecture without threading options through every
layer:

  attention_impl : "xla"          — chunked online-softmax in pure XLA ops
                                    (paper-faithful baseline; the online-
                                    softmax state round-trips HBM per key
                                    block);
                   "pallas_fused" — cost-model the validated Pallas flash
                                    kernel: the attention inner loop is
                                    tagged with a fused-region scope and
                                    LEO's parser prices it as VMEM-resident
                                    (inputs/outputs only), FLOPs unchanged.
  ssm_fused      : False          — discretize (a, bx) for the whole
                                    sequence up front (materializes
                                    B x S x d_inner x N in HBM);
                   True           — discretize per chunk inside the scan
                                    (transient, fuses into the chunk body).
                   Both shape the XLA chunk scan: every CPU process (the
                   tests, the dry-run, the hillclimb) and every step
                   sharded over several devices runs it.  A one-device
                   step in a TPU process scans in the Pallas selective-scan
                   kernel wherever it applies, and these two flags then
                   change nothing (`models/ssm.py`).
  moe_impl       : "global"       — routing over the global token axis
                                    (XLA inserts distributed sort/gather
                                    collectives);
                   "ep_shardmap"  — shard_map local routing + all-to-all
                                    expert parallelism over the "model"
                                    axis.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, replace


@dataclass(frozen=True)
class ModelFlags:
    attention_impl: str = "xla"
    ssm_fused: bool = False
    ssm_pallas: bool = False      # cost-model the Pallas selective-scan
                                  # kernel in the XLA chunk scan
    mlstm_pallas: bool = False    # cost-model the Pallas mlstm_chunkwise kernel
    sequence_parallel: bool = False  # shard residual-stream activations over
                                     # "model" between blocks: XLA turns the
                                     # Megatron activation all-reduces into
                                     # reduce-scatter + all-gather pairs
    moe_impl: str = "global"
    fsdp_threshold_mb: int = 128  # per-shard size above which weights are
                                  # dp-sharded; raise when bf16 params fit
                                  # per chip (FSDP re-gathers per microstep)


_FLAGS = ModelFlags()

# Scope marker the HLO parser recognizes as "this region runs as one Pallas
# kernel": instructions inside pay no intra-region HBM traffic.
FUSED_REGION_MARK = "pallas_fused_region"


def get_flags() -> ModelFlags:
    return _FLAGS


def set_flags(**kwargs) -> ModelFlags:
    global _FLAGS
    _FLAGS = replace(_FLAGS, **kwargs)
    return _FLAGS


@contextmanager
def flags(**kwargs):
    global _FLAGS
    prev = _FLAGS
    _FLAGS = replace(_FLAGS, **kwargs)
    try:
        yield _FLAGS
    finally:
        _FLAGS = prev
