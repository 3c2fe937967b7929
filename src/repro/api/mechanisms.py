"""Mechanism protocol — the privacy stage of the round pipeline.

A Mechanism owns (a) the per-round noise scale, calibrated to the Lemma-1
sensitivity of the broadcast theta~, and (b) the sampler that perturbs the
egress copies. Engines call ``scale`` once per round and ``sample`` once per
state leaf; they never branch on what kind of mechanism is installed. A
node-sharded round calls ``sample_rows`` instead: its shard's rows of the
same sample, bit for bit.

Calibrations (Laplace):
  'global'     — the paper's exact Lemma-1 L1 sensitivity 2*alpha_t*sqrt(n)*L
  'coordinate' — beyond-paper per-coordinate sensitivity 2*alpha_t*L, the
                 deployable choice at transformer scale where the sqrt(n)
                 factor of the global bound drowns learning (DESIGN.md #3).

>>> from repro.api import MECHANISMS
>>> mech = MECHANISMS.build("laplace", eps=2.0, L=1.0,
...                         calibration="coordinate")
>>> float(mech.scale(0.5, n=100))               # 2 * alpha_t * L / eps
0.5
>>> MECHANISMS.build("none").is_private
False
"""
from __future__ import annotations

import dataclasses
import math
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.api.registry import MECHANISMS

__all__ = ["Mechanism", "LaplaceMechanism", "GaussianMechanism", "NoNoise"]


@runtime_checkable
class Mechanism(Protocol):
    """Privacy stage: per-round scale + sampler for the broadcast noise."""

    noise_self: bool  # faithful Algorithm 1 mixes noisy theta~ for j == i too

    @property
    def is_private(self) -> bool: ...

    def scale(self, alpha_t, n: int) -> jax.Array:
        """Noise scale for a round with step size alpha_t and dimension n."""
        ...

    def sample(self, key: jax.Array, shape, scale, dtype=jnp.float32) -> jax.Array:
        """Draw the egress perturbation (zeros when scale == 0)."""
        ...

    def sample_rows(self, key: jax.Array, shape, row0, rows: int, scale,
                    dtype=jnp.float32) -> jax.Array:
        """Rows [row0, row0 + rows) of ``sample(key, shape, scale)``, bit
        for bit; rows >= shape[0] are zeros."""
        ...

    def rows_drawn(self, key: jax.Array, shape, rows: int,
                   dtype=jnp.float32) -> int:
        """Rows of ``shape`` that ``sample_rows`` draws for a block of
        ``rows``: ``rows`` when it draws the block alone."""
        ...


@dataclasses.dataclass(frozen=True)
class LaplaceMechanism:
    """The paper's mechanism: Laplace(S(t)/eps) on every broadcast (Eq. 8).

    eps = inf degrades exactly to the non-private path (scale 0, and the
    inverse-CDF sampler returns exact zeros), so sweeps over eps need no
    special casing.
    """

    eps: float = 1.0
    L: float = 1.0
    calibration: str = "global"   # 'global' (Lemma 1) | 'coordinate'
    noise_self: bool = True

    def __post_init__(self):
        if self.calibration not in ("global", "coordinate"):
            raise ValueError(f"unknown calibration {self.calibration!r}")

    @property
    def is_private(self) -> bool:
        return not math.isinf(self.eps)

    def scale(self, alpha_t, n: int) -> jax.Array:
        # deferred import: repro.core.__init__ imports the engines, which
        # import this module — a top-level core import would be circular
        from repro.core.privacy import laplace_scale
        if not self.is_private:
            return jnp.zeros(())
        if self.calibration == "coordinate":
            return 2.0 * jnp.asarray(alpha_t) * self.L / self.eps
        return laplace_scale(alpha_t, n, self.L, self.eps)

    def sample(self, key, shape, scale, dtype=jnp.float32):
        from repro.core.privacy import sample_laplace
        return sample_laplace(key, shape, scale, dtype)

    def sample_rows(self, key, shape, row0, rows, scale, dtype=jnp.float32):
        from repro.core.privacy import sample_laplace_rows
        return sample_laplace_rows(key, shape, row0, rows, scale, dtype)

    def rows_drawn(self, key, shape, rows, dtype=jnp.float32):
        from repro.core.privacy import rows_drawable
        return rows if rows_drawable(key, shape, dtype) else shape[0]


@dataclasses.dataclass(frozen=True)
class GaussianMechanism:
    """Beyond-paper (eps, delta)-DP: Gaussian noise with the classic
    analytic calibration sigma = sqrt(2 ln(1.25/delta)) * S2(t) / eps, where
    the L2 sensitivity of theta~ is S2(t) = 2 * alpha_t * L (no sqrt(n):
    the L2 ball of Assumption 2.3 is dimension-free)."""

    eps: float = 1.0
    delta: float = 1e-5
    L: float = 1.0
    noise_self: bool = True

    @property
    def is_private(self) -> bool:
        return not math.isinf(self.eps)

    def scale(self, alpha_t, n: int) -> jax.Array:
        if not self.is_private:
            return jnp.zeros(())
        c = math.sqrt(2.0 * math.log(1.25 / self.delta))
        return c * 2.0 * jnp.asarray(alpha_t) * self.L / self.eps

    def sample(self, key, shape, scale, dtype=jnp.float32):
        return jnp.asarray(scale, dtype) * jax.random.normal(key, shape, dtype)

    def sample_rows(self, key, shape, row0, rows, scale, dtype=jnp.float32):
        from repro.core.privacy import slice_rows
        return slice_rows(self.sample(key, shape, scale, dtype), row0, rows)

    def rows_drawn(self, key, shape, rows, dtype=jnp.float32):
        return shape[0]


@dataclasses.dataclass(frozen=True)
class NoNoise:
    """Explicit non-private mechanism (plain gossip averaging baseline)."""

    noise_self: bool = True

    @property
    def is_private(self) -> bool:
        return False

    def scale(self, alpha_t, n: int) -> jax.Array:
        return jnp.zeros(())

    def sample(self, key, shape, scale, dtype=jnp.float32):
        return jnp.zeros(shape, dtype)

    def sample_rows(self, key, shape, row0, rows, scale, dtype=jnp.float32):
        return jnp.zeros((rows,) + tuple(shape[1:]), dtype)

    def rows_drawn(self, key, shape, rows, dtype=jnp.float32):
        return 0


@MECHANISMS.register("laplace")
def _laplace(eps: float = 1.0, L: float = 1.0, calibration: str = "global",
             noise_self: bool = True) -> Mechanism:
    return LaplaceMechanism(eps=eps, L=L, calibration=calibration,
                            noise_self=noise_self)


@MECHANISMS.register("gaussian")
def _gaussian(eps: float = 1.0, L: float = 1.0, delta: float = 1e-5,
              noise_self: bool = True) -> Mechanism:
    return GaussianMechanism(eps=eps, delta=delta, L=L, noise_self=noise_self)


@MECHANISMS.register("none")
def _none(noise_self: bool = True) -> Mechanism:
    return NoNoise(noise_self=noise_self)
