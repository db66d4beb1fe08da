"""The comparison that decides `correct` for a training cell.

Each number is a relative gap between the program's reading and the plain
reference's, taken after the same first steps from the same seed:

  loss_gap        worst step's |loss - ref| / |ref|
  grad_norm_gap   worst step's |pre-clip gradient norm - ref| / ref
  grad_leaf_gap   worst leaf's |norm of the first clipped gradient - ref|
  update_leaf_gap worst leaf's |norm of the master weights' change - ref|

A leaf's gap is measured against the larger of the reference's norm of that
leaf and of the median leaf, since some gradients are all but zero.  A leaf
is one array of the parameter tree, and one layer of a stacked layer group.
Leaves whose first gradient in the reference is under a thousandth of the
median leaf's move under Adam by round-off alone; they are left out of the
change.
"""
from __future__ import annotations

from typing import Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

ROUNDOFF_SHARE = 1e-3


def _is_stacked(path) -> bool:
    return jax.tree_util.keystr(path).startswith("['groups']")


def norms(tree):
    """Frobenius norm of every leaf, on the device; a stacked layer group
    gives one norm per layer.  Traceable: call it inside `jax.jit`."""
    def norm(path, leaf):
        x = leaf.astype(jnp.float32)
        if _is_stacked(path):
            return jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, axis=1))
        return jnp.sqrt(jnp.sum(x * x))
    return jax.tree_util.tree_map_with_path(norm, tree)


def as_dict(norm_tree, scale: float = 1.0) -> Dict[str, float]:
    """{leaf name: norm * scale} from what `norms` returned."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(norm_tree))
    out: Dict[str, float] = {}
    for path, value in flat:
        name = jax.tree_util.keystr(path)
        if _is_stacked(path):
            out.update({f"{name}[{i}]": float(v) * scale
                        for i, v in enumerate(np.ravel(value))})
        else:
            out[name] = float(value) * scale
    return out


def worst_step_gap(prog, ref) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def worst_leaf_gap(prog: Mapping[str, float], ref: Mapping[str, float],
                   leaves=None) -> float:
    leaves = sorted(ref) if leaves is None else leaves
    floor = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in leaves)


def moving_leaves(first_grad: Mapping[str, float]):
    floor = ROUNDOFF_SHARE * float(np.median(list(first_grad.values())))
    return [k for k in sorted(first_grad) if first_grad[k] >= floor]


def gaps(prog: Mapping, ref: Mapping) -> Dict[str, float]:
    """Both sides: {"losses", "grad_norms": per step; "first_grad",
    "change": leaf norms}."""
    return {
        "loss_gap": worst_step_gap(prog["losses"], ref["losses"]),
        "grad_norm_gap": worst_step_gap(prog["grad_norms"],
                                        ref["grad_norms"]),
        "grad_leaf_gap": worst_leaf_gap(prog["first_grad"],
                                        ref["first_grad"]),
        "update_leaf_gap": worst_leaf_gap(
            prog["change"], ref["change"],
            moving_leaves(ref["first_grad"])),
    }
