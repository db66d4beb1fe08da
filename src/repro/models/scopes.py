"""Named scopes of the train step, one per model part.

Each is applied with `jax.named_scope`, so it appears as a component of
the `op_name` metadata of every HLO instruction traced inside it (under
autodiff and remat wrapped, as in `transpose(jvp(head_loss))`).  They
change metadata only (and some instruction names XLA derives from it),
never the compiled program's operations, and let a profile of the step
be attributed to model parts (`repro.core.cct.scope_of`,
`seconds_by_scope`).
"""
from __future__ import annotations

EMBED = "embed"          # token embedding
ATTN = "attn"            # attention mixer (GQA, MLA; the hybrid's half)
SSM = "ssm"              # SSM mixer (the hybrid's other half)
MLSTM = "mlstm"          # xLSTM matrix-memory mixer
SLSTM = "slstm"          # xLSTM scalar-memory mixer
MLP = "mlp"              # dense FFN
MOE = "moe"              # MoE FFN
NORM = "norm"            # block RMSNorms
HEAD_LOSS = "head_loss"  # final norm, unembedding, log-softmax and NLL
OPTIMIZER = "optimizer"  # clipping, schedule and AdamW update

MODEL_SCOPES = (EMBED, ATTN, SSM, MLSTM, SLSTM, MLP, MOE, NORM, HEAD_LOSS,
                OPTIMIZER)

# Layer descriptors' mixer and FFN kinds -> their scope.
KIND_SCOPES = {"attn": ATTN, "mla": ATTN, "ssm": SSM, "mlstm": MLSTM,
               "slstm": SLSTM, "mlp": MLP, "moe": MOE}
