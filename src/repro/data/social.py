"""Synthetic social-data stream matching the paper's simulation scale.

The paper uses 100,000 real social data points of dimensionality 10,000
(unreleased). We generate a stream with the same scale and task shape:
a sparse ground-truth w* (only `sparsity_true` fraction of features carry
signal — "a person's height cannot contribute to predicting his taste"),
features x normalized per the paper's pretreatment, labels y = sign(<w*,x>)
with optional flip noise. Each node's per-round sample is disjoint from all
others (fresh randomness per (t, i)) — the condition for Theorem 1's
parallel composition.

Streams are generated in jit-able chunks so a 100k x 10k simulation never
materializes 4 GB at once. Sampling is keyed per ABSOLUTE round (one
fold_in per t, vmapped), so ``chunk(a, b)`` returns the same rounds no
matter how the horizon is partitioned — the property `repro.api.run`
relies on for checkpoint resume and for sim-vs-dist bit-identity under
different chunk sizes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterator

import jax
import jax.numpy as jnp


# Labels are the sign of a float32 contraction. Pinning it to HIGHEST keeps
# a stream's labels a function of its seed alone: at the ambient default a
# TPU contracts in bf16 passes, and a logit near zero could then flip sign
# between runs made under different matmul-precision settings.
LABEL_PRECISION = jax.lax.Precision.HIGHEST


def labels_from_logits(logits: jax.Array) -> jax.Array:
    """y = +1 iff <w*, x> >= 0 — an exact-zero logit maps to +1, never to
    the invalid label 0 (jnp.sign(0) == 0 would silently break the hinge
    workload: a 0 label zeroes the gradient AND can never be predicted)."""
    return jnp.where(logits >= 0, 1.0, -1.0).astype(jnp.float32)


def round_keys(base: jax.Array, t0: int, t1: int) -> jax.Array:
    """One PRNG key per absolute round in [t0, t1) — chunk-boundary
    invariant: the key for round t never depends on where chunks split."""
    return jax.vmap(lambda t: jax.random.fold_in(base, t))(jnp.arange(t0, t1))


@functools.lru_cache(maxsize=128)
def _w_true(n: int, sparsity_true: float, seed: int) -> jax.Array:
    kw, km = jax.random.split(jax.random.PRNGKey(seed))
    mask = jax.random.uniform(km, (n,)) < sparsity_true
    w = jax.random.normal(kw, (n,)) * mask
    return (w / jnp.maximum(jnp.linalg.norm(w), 1e-9)).astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class SocialStream:
    n: int
    nodes: int
    rounds: int
    sparsity_true: float = 0.05
    label_noise: float = 0.0
    seed: int = 0

    # every round touches only samples that arrive at that round — the
    # Theorem-1 parallel-composition condition the PrivacyAccountant reads
    disjoint: bool = True

    def w_true(self) -> jax.Array:
        # cached across chunk() calls — the ground truth is a pure function
        # of (n, sparsity_true, seed) and used to be recomputed per chunk
        return _w_true(self.n, self.sparsity_true, self.seed)

    def chunk(self, t0: int, t1: int) -> tuple[jax.Array, jax.Array]:
        """Rounds [t0, t1): returns xs (t1-t0, m, n), ys (t1-t0, m)."""
        w = self.w_true()
        keys = round_keys(jax.random.PRNGKey(self.seed + 1), t0, t1)
        kx, kn = jax.vmap(lambda k: tuple(jax.random.split(k)))(keys)
        x = jax.vmap(
            lambda k: jax.random.normal(k, (self.nodes, self.n))
        )(kx) / jnp.sqrt(self.n)
        logits = jnp.einsum("n,tmn->tm", w, x, precision=LABEL_PRECISION)
        y = labels_from_logits(logits)
        if self.label_noise > 0:
            flip = jax.vmap(
                lambda k: jax.random.uniform(k, (self.nodes,))
            )(kn) < self.label_noise
            y = jnp.where(flip, -y, y)
        return x.astype(jnp.float32), y.astype(jnp.float32)

    def chunks(self, chunk_rounds: int = 512) -> Iterator[tuple[jax.Array, jax.Array]]:
        t = 0
        while t < self.rounds:
            t1 = min(t + chunk_rounds, self.rounds)
            yield self.chunk(t, t1)
            t = t1


def make_social_stream(cfg) -> SocialStream:
    """From a configs.social_linear.SocialLinearConfig."""
    return SocialStream(
        n=cfg.n, nodes=cfg.nodes, rounds=cfg.rounds,
        sparsity_true=cfg.sparsity_true, seed=cfg.seed,
    )
