"""The comparisons that decide ``correct``: gaps between the program's
readings and the reference's, each held to its limit."""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def leaf_names(shapes) -> List[str]:
    """One name per leaf of a tree of shapes, and per layer of a leaf
    stacked on a leading layer axis (``layers/...``): a fault in one layer
    is not averaged over the others."""
    names = []
    is_shape = lambda x: isinstance(x, tuple)
    for path, shape in jax.tree.flatten_with_path(shapes,
                                                  is_leaf=is_shape)[0]:
        name = "/".join(p.key for p in path)
        if name.startswith("layers/"):
            names += [f"{name}[{i}]" for i in range(shape[0])]
        else:
            names.append(name)
    return names


@jax.jit
def leaf_norms(tree):
    """The float32 L2 norm of every leaf (of every layer), as one vector
    in ``leaf_names`` order."""
    out = []
    for path, x in jax.tree.flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        if path[0].key == "layers":
            out.append(jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)),
                                        axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


@jax.jit
def change_norms(new, old):
    return leaf_norms(jax.tree.map(jnp.subtract, new, old))


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> Dict:
    """Worst leaf's |‖prog‖ − ‖ref‖| over the larger of the reference's
    norm of that leaf and of the median leaf."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    scale = np.maximum(ref, np.median(ref[keep]))
    gap = np.where(keep, np.abs(prog - ref) / scale, 0.0)
    if not np.all(np.isfinite(prog[keep])):
        return {"value": float("inf"), "leaf": int(np.argmax(
            ~np.isfinite(prog) & keep))}
    i = int(np.argmax(gap))
    return {"value": float(gap[i]), "leaf": i}


def moved(ref_grad_norms: np.ndarray, share: float = 1e-3) -> np.ndarray:
    """Leaves whose first gradient in the reference is not nought to
    rounding: at least ``share`` of the median leaf's."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= share * np.median(g)
