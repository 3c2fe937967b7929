"""Differential privacy machinery (paper §III).

Implements:
  * Lemma 1 sensitivity:  S(t) <= 2 * alpha_t * sqrt(n) * L
  * Laplace noise with scale mu = S(t) / eps       (Eq. 8)
  * per-round eps-DP (Lemma 2) + parallel composition across rounds (Thm 1,
    valid because each round consumes disjoint stream entries)
  * gradient clipping that ENFORCES the bound ||g||_2 <= L that the paper
    assumes (Assumption 2.3) — without clipping the DP guarantee is vacuous
    for unbounded losses.

TPU adaptation: Laplace sampling uses the inverse-CDF transform of a uniform
(threefry) sample — branch-free and vectorizes on VPU; no rejection sampling.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.extend.random import threefry2x32_p

__all__ = [
    "sensitivity",
    "laplace_scale",
    "sample_laplace",
    "sample_laplace_rows",
    "rows_drawable",
    "row_block_counters",
    "slice_rows",
    "sample_laplace_tree",
    "clip_by_l2",
    "PrivacyConfig",
    "PrivacyAccountant",
]


def sensitivity(alpha_t: float | jax.Array, n: int, L: float) -> jax.Array:
    """Lemma 1: S(t) <= 2 * alpha_t * sqrt(n) * L  (L1 sensitivity of theta)."""
    return 2.0 * jnp.asarray(alpha_t) * math.sqrt(n) * L


def laplace_scale(alpha_t: float | jax.Array, n: int, L: float, eps: float) -> jax.Array:
    """mu = S(t) / eps (Eq. 8). eps = inf => scale 0 (non-private)."""
    if math.isinf(eps):
        return jnp.zeros(())
    return sensitivity(alpha_t, n, L) / eps


_U_MIN, _U_MAX = -0.5 + 1e-7, 0.5     # the uniform's range for Laplace


def _laplace_from_uniform(u, scale, dtype):
    noise = -jnp.sign(u) * jnp.log1p(-2.0 * jnp.abs(u))
    return jnp.asarray(scale, dtype) * noise


def sample_laplace(key: jax.Array, shape, scale, dtype=jnp.float32) -> jax.Array:
    """Laplace(0, scale) via inverse CDF: x = -scale * sign(u) * log1p(-2|u|).

    u ~ Uniform(-1/2, 1/2). Branch-free; exact for scale == 0 (returns zeros).
    """
    u = jax.random.uniform(key, shape, dtype=dtype, minval=_U_MIN, maxval=_U_MAX)
    return _laplace_from_uniform(u, scale, dtype)


def _row_ids(row0, rows: int, ndim: int) -> jax.Array:
    return jnp.asarray(row0) + jax.lax.broadcasted_iota(
        jnp.int32, (rows,) + (1,) * (ndim - 1), 0)


def slice_rows(full: jax.Array, row0, rows: int) -> jax.Array:
    """Rows [row0, row0 + rows) of ``full``; rows past its end read 0.

    Zero-pads BEFORE the slice: dynamic_slice clamps its start, so an
    unpadded last block would read rows of the block before it.
    """
    m = full.shape[0]
    full = jnp.pad(full, [(0, rows)] + [(0, 0)] * (full.ndim - 1))
    block = jax.lax.dynamic_slice_in_dim(full, row0, rows, axis=0)
    return jnp.where(_row_ids(row0, rows, full.ndim) < m, block,
                     jnp.zeros((), full.dtype))


def rows_drawable(key: jax.Array, shape, dtype=jnp.float32) -> bool:
    """Whether `sample_laplace_rows` can draw a row block alone.

    It can when element i of a draw from ``key`` is threefry2x32 of the
    flat index i (``jax_threefry_partitionable``), the key is a raw
    threefry key, the draw is float32 and a row's width fits 32 bits.
    """
    return bool(jax.config.jax_threefry_partitionable
                and jax.config.jax_default_prng_impl == "threefry2x32"
                and jnp.shape(key) == (2,)
                and jnp.result_type(key) == jnp.uint32
                and jnp.dtype(dtype) == jnp.float32
                and math.prod(shape[1:]) < 2 ** 32)


def _mul_wide(a: jax.Array, b: int) -> tuple[jax.Array, jax.Array]:
    """(hi, lo) uint32 words of the 64-bit product a * b, a uint32, b < 2**32."""
    a_lo, a_hi = a & 0xFFFF, a >> 16
    b_lo, b_hi = jnp.uint32(b & 0xFFFF), jnp.uint32(b >> 16)
    ll, lh, hl = a_lo * b_lo, a_lo * b_hi, a_hi * b_lo
    mid = (ll >> 16) + (lh & 0xFFFF) + (hl & 0xFFFF)
    hi = a_hi * b_hi + (lh >> 16) + (hl >> 16) + (mid >> 16)
    return hi, a * jnp.uint32(b)


def row_block_counters(row0, rows: int, n: int) -> tuple[jax.Array, jax.Array]:
    """(hi, lo) uint32 words of the flat index (row0 + r) * n + j, the
    threefry counter of element (row0 + r, j) of an (m, n) draw, as
    (rows, n) arrays; the low word carries into the high one."""
    g = jnp.asarray(row0).astype(jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, (rows, 1), 0)
    base_hi, base_lo = _mul_wide(g, n)
    lo = base_lo + jax.lax.broadcasted_iota(jnp.uint32, (rows, n), 1)
    hi = base_hi + (lo < base_lo).astype(jnp.uint32)
    return hi, lo


@jax.jit
def _uniform_f32(bits: jax.Array) -> jax.Array:
    """jax.random.uniform's float32 transform of 32 random bits onto
    [_U_MIN, _U_MAX): 23 mantissa bits under 1.0's exponent give [1, 2),
    shifted to [0, 1) and scaled. Jitted as jax.random.uniform is, so that
    an eager call compiles (and contracts) the same arithmetic."""
    bits = jax.lax.shift_right_logical(bits, jnp.uint32(9))
    one = jnp.asarray(jnp.array(1.0, jnp.float32).view(jnp.uint32))
    floats = jax.lax.bitcast_convert_type(bits | one, jnp.float32) - 1.0
    lo, hi = jnp.float32(_U_MIN), jnp.float32(_U_MAX)
    return jax.lax.max(lo, floats * (hi - lo) + lo)


def sample_laplace_rows(key: jax.Array, shape, row0, rows: int, scale,
                        dtype=jnp.float32) -> jax.Array:
    """Rows [row0, row0 + rows) of ``sample_laplace(key, shape, scale)``,
    bit for bit, drawn without the rest; rows >= shape[0] come out 0.

    ``row0`` may be traced (a shard's ``axis_index * block``). Where
    `rows_drawable` says no, the whole sample is drawn and sliced.
    """
    if not rows_drawable(key, shape, dtype):
        return slice_rows(sample_laplace(key, shape, scale, dtype), row0, rows)
    m, n = shape[0], math.prod(shape[1:])
    hi, lo = row_block_counters(row0, rows, n)
    bits1, bits2 = threefry2x32_p.bind(key[0], key[1], hi, lo)
    noise = _laplace_from_uniform(_uniform_f32(bits1 ^ bits2), scale, dtype)
    noise = jnp.where(_row_ids(row0, rows, 2) < m, noise, jnp.zeros((), dtype))
    return noise.reshape((rows,) + tuple(shape[1:]))


def sample_laplace_tree(key: jax.Array, tree: Any, scale, dtype=None) -> Any:
    """One independent Laplace sample per leaf of a pytree (same scale)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    noisy = [
        sample_laplace(k, jnp.shape(leaf), scale, dtype or jnp.result_type(leaf))
        for k, leaf in zip(keys, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, noisy)


def clip_by_l2(tree: Any, max_norm: float) -> tuple[Any, jax.Array]:
    """Scale the whole pytree so its global L2 norm is <= max_norm.

    Enforces Assumption 2.3 (||g|| <= L); returns (clipped, pre-clip norm).
    """
    sq = sum(jnp.sum(jnp.square(leaf.astype(jnp.float32))) for leaf in jax.tree_util.tree_leaves(tree))
    norm = jnp.sqrt(sq)
    factor = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree_util.tree_map(lambda x: (x * factor).astype(x.dtype), tree), norm


@dataclasses.dataclass(frozen=True)
class PrivacyConfig:
    """All knobs of the paper's privacy mechanism.

    eps:        per-round privacy budget (paper sweeps 0.1 / 1 / 10 / inf).
    L:          subgradient bound (Assumption 2.3), enforced by clipping.
    noise_self: faithful default True — Algorithm 1 mixes the *noisy* theta
                for every j including j == i. False is the beyond-paper
                variant (own theta needs no network hop => no noise).
    clip_style: 'global' = paper's Lemma 1 scale 2*alpha*sqrt(n)*L on the
                whole vector; 'coordinate' = beyond-paper per-coordinate
                sensitivity 2*alpha*L_inf (tighter when gradients are dense).
    """

    eps: float = 1.0
    L: float = 1.0
    noise_self: bool = True
    clip_style: str = "global"

    @property
    def is_private(self) -> bool:
        return not math.isinf(self.eps)

    def scale_for(self, alpha_t, n: int) -> jax.Array:
        if not self.is_private:
            return jnp.zeros(())
        if self.clip_style == "coordinate":
            return 2.0 * jnp.asarray(alpha_t) * self.L / self.eps
        return laplace_scale(alpha_t, n, self.L, self.eps)


@dataclasses.dataclass
class PrivacyAccountant:
    """Tracks the cumulative guarantee.

    Theorem 1 (parallel composition, McSherry): because round t touches only
    the stream entries that arrive at round t (disjoint across rounds), the
    T-round algorithm is eps-DP overall, NOT T*eps. We additionally track the
    pessimistic sequential-composition number for transparency.

    `repro.api.run` threads one accountant through every run: ``step(k)``
    after each chunk of k rounds, ``ledger(T)`` for the per-round eps
    trajectory in the RunResult, ``summary()`` for the final record.
    """

    eps_per_round: float
    rounds: int = 0
    disjoint_streams: bool = True
    node_rounds: Any = None   # optional (m,) per-node participated rounds

    def __post_init__(self):
        if self.eps_per_round < 0:
            raise ValueError("eps_per_round must be >= 0")
        if self.rounds < 0:
            raise ValueError("rounds must be >= 0")

    def step(self, k: int = 1, participation: Any = None) -> None:
        """Advance ``k`` rounds; ``participation`` (optional, (m,) ints)
        says how many of them each node actually spent eps in.

        A node only releases a noised broadcast in rounds it participates
        in (repro.faults: crashed rounds draw no attention from the
        adversary), so charging it for the full chunk overstates its spend.
        The first masked call starts per-node tracking, back-filling rounds
        stepped before it as full participation.
        """
        if k < 0:
            raise ValueError("cannot step a negative number of rounds")
        prior = self.rounds
        self.rounds += k
        if participation is not None:
            import numpy as np
            part = np.asarray(participation, np.int64).ravel()
            if part.size and ((part < 0).any() or (part > k).any()):
                raise ValueError(
                    f"participation counts must be in [0, {k}] for a "
                    f"{k}-round step; got range "
                    f"[{part.min()}, {part.max()}]")
            if self.node_rounds is None:
                self.node_rounds = np.full(part.shape, prior, np.int64)
            self.node_rounds = self.node_rounds + part
        elif self.node_rounds is not None:
            self.node_rounds = self.node_rounds + k

    def guarantee_at(self, rounds: int) -> float:
        """Cumulative eps after ``rounds`` rounds.

        0 rounds => 0.0 (nothing has been released yet — the pre-fix code
        claimed eps_per_round before the first broadcast). Under Theorem 1
        the guarantee is flat at eps_per_round for every rounds >= 1; the
        sequential fallback composes linearly.
        """
        if rounds == 0:
            return 0.0
        if self.disjoint_streams:
            return self.eps_per_round  # Thm 1
        return self.eps_per_round * rounds  # sequential fallback

    @property
    def guarantee(self) -> float:
        return self.guarantee_at(self.rounds)

    def ledger(self, rounds: int | None = None) -> list[float]:
        """Per-round cumulative eps trajectory [guarantee_at(1) ..
        guarantee_at(T)] — what `repro.api.run` records in RunResult."""
        T = self.rounds if rounds is None else rounds
        return [self.guarantee_at(t) for t in range(1, T + 1)]

    def per_node_guarantee(self):
        """(m,) cumulative eps per node, or None without participation
        tracking. Parallel composition: a node that ever participated is at
        eps_per_round, one that never did is at 0; sequential composes its
        own participated rounds linearly."""
        if self.node_rounds is None:
            return None
        import numpy as np
        counts = np.asarray(self.node_rounds, np.int64)
        if self.disjoint_streams:
            return np.where(counts > 0, self.eps_per_round, 0.0)
        return self.eps_per_round * counts.astype(np.float64)

    def summary(self) -> dict:
        out = {
            "eps_per_round": self.eps_per_round,
            "rounds": self.rounds,
            "eps_total": self.guarantee,
            "composition": "parallel (disjoint)" if self.disjoint_streams else "sequential",
        }
        if self.node_rounds is not None:
            per_node = self.per_node_guarantee()
            out["participated_rounds"] = [int(v) for v in self.node_rounds]
            out["eps_per_node_max"] = float(per_node.max())
            out["eps_per_node_min"] = float(per_node.min())
        return out
