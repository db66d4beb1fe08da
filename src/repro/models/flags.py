"""Model-level optimization flags (the §Perf hillclimb levers).

Three process-wide switches, so that LEO's dry-run can lower baseline and
optimized variants of one architecture without threading options through
every layer.  The hillclimb's variants (`launch/hillclimb.CELLS`) and the
dry-run's `--flags` set them, inside `flags(...)`; nothing else does.

  attention_impl : "xla"          — chunked online-softmax in pure XLA ops
                                    (paper-faithful baseline; the online-
                                    softmax state round-trips HBM per key
                                    block);
                   "pallas_fused" — cost-model the validated Pallas flash
                                    kernel: the attention inner loop is
                                    tagged with a fused-region scope and
                                    LEO's parser prices it as VMEM-resident
                                    (inputs/outputs only), FLOPs unchanged.
  ssm_impl       : "xla"          — discretize (a, bx) for the whole
                                    sequence up front (materializes
                                    B x S x d_inner x N in HBM);
                   "chunk_fused"  — discretize per chunk inside the scan
                                    (transient, fuses into the chunk body);
                   "pallas_fused" — "chunk_fused", with the chunk scan
                                    tagged as the Pallas selective-scan
                                    kernel, priced as attention's is.
                   All three shape the XLA chunk scan: every CPU process
                   (the tests, the dry-run, the hillclimb) and every step
                   sharded over several devices runs it.  Where the Pallas
                   kernels' placement rule holds (`kernels/placement.py`),
                   a step scans in the selective-scan kernel wherever it
                   applies, and this flag changes nothing (`models/ssm.py`).
  moe_impl       : "global"       — routing over the global token axis
                                    (XLA inserts distributed sort/gather
                                    collectives);
                   "ep_shardmap"  — shard_map local routing + all-to-all
                                    expert parallelism over the "model"
                                    axis of the mesh the step runs over.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, replace

_VALUES = {"attention_impl": ("xla", "pallas_fused"),
           "ssm_impl": ("xla", "chunk_fused", "pallas_fused"),
           "moe_impl": ("global", "ep_shardmap")}


@dataclass(frozen=True)
class ModelFlags:
    attention_impl: str = "xla"
    ssm_impl: str = "xla"
    moe_impl: str = "global"

    def __post_init__(self):
        for name, allowed in _VALUES.items():
            if getattr(self, name) not in allowed:
                raise ValueError(f"{name}={getattr(self, name)!r}: "
                                 f"expected one of {allowed}")


_FLAGS = ModelFlags()

# Scope marker the HLO parser recognizes as "this region runs as one Pallas
# kernel": instructions inside pay no intra-region HBM traffic.
FUSED_REGION_MARK = "pallas_fused_region"


def get_flags() -> ModelFlags:
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
