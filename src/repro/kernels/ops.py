"""Public jit'd wrappers around the Pallas kernels.

On the CPU the kernels execute with ``interpret=True`` so every test checks
the actual kernel body; on a TPU they compile to Mosaic. The
wrappers also handle padding/reshaping from arbitrary parameter pytrees to
the kernels' (rows, 128) tiled layout.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import pdomd_update as _pdomd
from repro.kernels import hinge_grad as _hinge

LANE = _pdomd.LANE
SUBLANE = _pdomd.SUBLANE


def _default_interpret() -> bool:
    """Interpret only on the CPU; any other platform compiles the kernels,
    so a device that cannot run them fails instead of silently
    interpreting."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# flat (rows, 128) <-> pytree plumbing
# ---------------------------------------------------------------------------

def flat_size(tree: Any) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def padded_rows(size: int) -> int:
    rows = -(-size // LANE)
    return -(-rows // SUBLANE) * SUBLANE


def tree_to_tiles(tree: Any) -> jax.Array:
    """Flatten a pytree into one (rows, 128) f32 array (zero padded)."""
    leaves = [l.reshape(-1).astype(jnp.float32) for l in jax.tree_util.tree_leaves(tree)]
    flat = jnp.concatenate(leaves) if len(leaves) > 1 else leaves[0]
    rows = padded_rows(flat.size)
    pad = rows * LANE - flat.size
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANE)


def tiles_to_tree(tiles: jax.Array, tree_like: Any) -> Any:
    """Inverse of :func:`tree_to_tiles` (casts back to each leaf's dtype)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree_like)
    flat = tiles.reshape(-1)
    out, off = [], 0
    for l in leaves:
        sz = int(np.prod(l.shape))
        out.append(flat[off:off + sz].reshape(l.shape).astype(l.dtype))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def pdomd_update(theta_self, theta_prev, theta_next, grad, alpha, lam,
                 self_weight=0.5, nbr_weight=0.25, *, interpret: bool | None = None,
                 block_rows: int = _pdomd.DEFAULT_BLOCK_ROWS):
    """Fused mix + OMD step + L1 prox on (rows, 128) tiles."""
    if interpret is None:
        interpret = _default_interpret()
    return _pdomd.pdomd_update(
        theta_self, theta_prev, theta_next, grad,
        jnp.asarray(alpha, jnp.float32), jnp.asarray(lam, jnp.float32),
        jnp.asarray(self_weight, jnp.float32), jnp.asarray(nbr_weight, jnp.float32),
        block_rows=block_rows, interpret=interpret,
    )


def hinge_grad(x, y, w, *, interpret: bool | None = None,
               block_b: int = _hinge.DEFAULT_BLOCK_B):
    """Fused hinge loss + subgradient. Returns (loss, grad, margin)."""
    if interpret is None:
        interpret = _default_interpret()
    return _hinge.hinge_grad(x, y, w, block_b=block_b, interpret=interpret)
