"""Stream protocol — the data-scenario stage of the experiment pipeline.

A Stream owns WHERE the per-round samples come from; everything downstream
(clip -> noise -> mix -> local rule) is scenario-agnostic. Like the other
`repro.api` protocols, streams resolve by name through a registry
(`STREAMS`) so a new workload registers once and is immediately reachable
from `RunSpec(stream=...)`, the train/dryrun CLIs (``--stream`` /
``--stream-opt``), and `repro.api.run` — without touching engine or runner
code.

Every stream emits fixed-shape, jit-friendly chunks::

    xs, ys = stream.chunk(t0, t1)     # xs (t1-t0, m, n), ys (t1-t0, m)

keyed per ABSOLUTE round, so the data for round t never depends on how the
horizon is partitioned into chunks (the property checkpoint resume and the
sim-vs-dist equivalence tests rely on).

Built-in scenarios:

  social_sparse  — the paper's §V workload: fixed sparse w*, normalized
                   gaussian features, optional label flips.
  drift          — w* is NON-stationary: its sparse support reshuffles
                   (or rotates) every ``period`` rounds, the adversarial
                   regime online regret bounds are actually about.
  heterogeneous  — per-node feature scales and label-noise rates drawn
                   from a seeded distribution: every data center sees its
                   own population (Tekin & van der Schaar's context-
                   dependent nodes).
  bursty         — per-(t, i) sample counts from a seeded heavy-tailed
                   (discrete Pareto) distribution; a round's emitted sample
                   is the mean of its burst, so busy rounds carry lower-
                   variance evidence.

>>> from repro.api.streams import STREAMS
>>> {"social_sparse", "drift", "heterogeneous", "bursty"} <= set(STREAMS.names())
True
>>> s = STREAMS.build("drift", n=32, nodes=4, rounds=64, seed=0)
>>> xs, ys = s.chunk(0, 8)
>>> xs.shape, ys.shape
((8, 4, 32), (8, 4))
>>> b = STREAMS.build("bursty", n=16, nodes=2, rounds=32, seed=1)
>>> int(b.counts(0, 32).min()) >= 1 and int(b.counts(0, 32).max()) <= b.burst_max
True
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.api.registry import STREAMS
from repro.data.social import (LABEL_PRECISION, SocialStream,
                               labels_from_logits, round_keys)

__all__ = [
    "Stream",
    "STREAMS",
    "SocialStream",
    "DriftStream",
    "HeterogeneousStream",
    "BurstyStream",
]


@runtime_checkable
class Stream(Protocol):
    """Data-scenario stage: per-round samples for every node.

    ``disjoint`` declares whether round t touches only samples that arrive
    at round t (true for every built-in stream) — the Theorem-1 parallel-
    composition condition `repro.api.run` hands to the PrivacyAccountant.
    """

    n: int        # feature dimension
    nodes: int    # m data centers
    rounds: int   # stream length (the run horizon)
    disjoint: bool

    def chunk(self, t0: int, t1: int) -> tuple[jax.Array, jax.Array]:
        """Rounds [t0, t1): xs (t1-t0, m, n), ys (t1-t0, m) with y in ±1."""
        ...


def _chunks(stream: Stream, chunk_rounds: int) -> Iterator[tuple[jax.Array, jax.Array]]:
    t = 0
    while t < stream.rounds:
        t1 = min(t + chunk_rounds, stream.rounds)
        yield stream.chunk(t, t1)
        t = t1


@dataclasses.dataclass(frozen=True)
class DriftStream:
    """Non-stationary ground truth: w* changes every ``period`` rounds.

    mode='reshuffle' draws a fresh sparse w* per phase (abrupt concept
    drift); mode='rotate' rolls the phase-0 w* by ``period``-proportional
    offsets, so the support wanders through the feature space but keeps its
    geometry (gradual drift). Labels always come from the CURRENT phase's
    w*, so a learner that stops adapting goes stale.
    """

    n: int
    nodes: int
    rounds: int
    period: int = 64
    mode: str = "reshuffle"      # 'reshuffle' | 'rotate'
    sparsity_true: float = 0.05
    label_noise: float = 0.0
    seed: int = 0
    disjoint: bool = True

    def __post_init__(self):
        if self.period < 1:
            raise ValueError("drift period must be >= 1")
        if self.mode not in ("reshuffle", "rotate"):
            raise ValueError(f"unknown drift mode {self.mode!r}")

    def _base(self) -> SocialStream:
        return SocialStream(n=self.n, nodes=self.nodes, rounds=self.rounds,
                            sparsity_true=self.sparsity_true, seed=self.seed)

    def w_true_at(self, t) -> jax.Array:
        """Ground truth in effect at round t (vmap/jit friendly)."""
        phase = jnp.asarray(t) // self.period
        if self.mode == "rotate":
            w0 = self._base().w_true()
            # roll by a phase-proportional offset, coprime-ish with n so the
            # support visits the whole feature space before repeating
            shift = (phase * (self.n // 4 + 1)) % self.n
            return jnp.roll(w0, shift)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), phase)
        kw, km = jax.random.split(key)
        mask = jax.random.uniform(km, (self.n,)) < self.sparsity_true
        w = jax.random.normal(kw, (self.n,)) * mask
        return (w / jnp.maximum(jnp.linalg.norm(w), 1e-9)).astype(jnp.float32)

    def chunk(self, t0: int, t1: int) -> tuple[jax.Array, jax.Array]:
        keys = round_keys(jax.random.PRNGKey(self.seed + 1), t0, t1)
        kx, kn = jax.vmap(lambda k: tuple(jax.random.split(k)))(keys)
        x = jax.vmap(
            lambda k: jax.random.normal(k, (self.nodes, self.n))
        )(kx) / jnp.sqrt(self.n)
        W = jax.vmap(self.w_true_at)(jnp.arange(t0, t1))       # (T, n)
        y = labels_from_logits(jnp.einsum("tn,tmn->tm", W, x,
                                          precision=LABEL_PRECISION))
        if self.label_noise > 0:
            flip = jax.vmap(
                lambda k: jax.random.uniform(k, (self.nodes,))
            )(kn) < self.label_noise
            y = jnp.where(flip, -y, y)
        return x.astype(jnp.float32), y.astype(jnp.float32)

    def chunks(self, chunk_rounds: int = 512):
        return _chunks(self, chunk_rounds)


@dataclasses.dataclass(frozen=True)
class HeterogeneousStream:
    """Per-node populations: each data center has its own feature scale and
    label-noise rate, drawn once from a seeded distribution.

    Feature scales are lognormal (sigma = ``scale_spread``) around the
    social_sparse normalization, so some nodes see loud features and some
    quiet ones; per-node flip rates are Uniform(0, ``noise_max``). The
    ground truth w* is SHARED — the consensus the gossip step is supposed
    to recover despite the heterogeneity.
    """

    n: int
    nodes: int
    rounds: int
    scale_spread: float = 0.5
    noise_max: float = 0.2
    sparsity_true: float = 0.05
    seed: int = 0
    disjoint: bool = True

    def _base(self) -> SocialStream:
        return SocialStream(n=self.n, nodes=self.nodes, rounds=self.rounds,
                            sparsity_true=self.sparsity_true, seed=self.seed)

    def node_scales(self) -> jax.Array:
        """(m,) per-node lognormal feature scales."""
        k = jax.random.fold_in(jax.random.PRNGKey(self.seed), 7)
        return jnp.exp(
            self.scale_spread * jax.random.normal(k, (self.nodes,))
        ).astype(jnp.float32)

    def node_noise_rates(self) -> jax.Array:
        """(m,) per-node label-flip probabilities in [0, noise_max)."""
        k = jax.random.fold_in(jax.random.PRNGKey(self.seed), 8)
        return (self.noise_max
                * jax.random.uniform(k, (self.nodes,))).astype(jnp.float32)

    def chunk(self, t0: int, t1: int) -> tuple[jax.Array, jax.Array]:
        w = self._base().w_true()
        scales = self.node_scales()
        rates = self.node_noise_rates()
        keys = round_keys(jax.random.PRNGKey(self.seed + 1), t0, t1)
        kx, kn = jax.vmap(lambda k: tuple(jax.random.split(k)))(keys)
        x = jax.vmap(
            lambda k: jax.random.normal(k, (self.nodes, self.n))
        )(kx) * scales[None, :, None] / jnp.sqrt(self.n)
        y = labels_from_logits(jnp.einsum("n,tmn->tm", w, x,
                                          precision=LABEL_PRECISION))
        flip = jax.vmap(
            lambda k: jax.random.uniform(k, (self.nodes,))
        )(kn) < rates[None, :]
        y = jnp.where(flip, -y, y)
        return x.astype(jnp.float32), y.astype(jnp.float32)

    def chunks(self, chunk_rounds: int = 512):
        return _chunks(self, chunk_rounds)


@dataclasses.dataclass(frozen=True)
class BurstyStream:
    """Heavy-tailed per-round sample counts (big-data arrival bursts).

    For every (round, node) a count c is drawn from a capped discrete
    Pareto: c = min(floor(u^(-1/tail)), burst_max) with u ~ Uniform(0, 1),
    so c >= 1 always and P(c >= k) ~ k^-tail. The emitted sample is the
    MEAN of the c fresh samples in the burst (labels come from the mean
    feature), so busy rounds deliver lower-variance, smaller-norm evidence
    — the shape stays (T, m, n) and everything downstream is unchanged.
    ``counts`` exposes the burst sizes for inspection.
    """

    n: int
    nodes: int
    rounds: int
    burst_max: int = 8
    tail: float = 1.5            # Pareto tail index; smaller = heavier
    sparsity_true: float = 0.05
    seed: int = 0
    disjoint: bool = True

    def __post_init__(self):
        if self.burst_max < 1:
            raise ValueError("burst_max must be >= 1")
        if self.tail <= 0:
            raise ValueError("tail must be > 0")

    def _base(self) -> SocialStream:
        return SocialStream(n=self.n, nodes=self.nodes, rounds=self.rounds,
                            sparsity_true=self.sparsity_true, seed=self.seed)

    def counts(self, t0: int, t1: int) -> jax.Array:
        """(t1-t0, m) burst sizes in [1, burst_max], heavy-tailed."""
        keys = round_keys(jax.random.PRNGKey(self.seed + 2), t0, t1)
        u = jax.vmap(
            lambda k: jax.random.uniform(k, (self.nodes,),
                                         minval=1e-7, maxval=1.0)
        )(keys)
        c = jnp.floor(u ** (-1.0 / self.tail))
        return jnp.clip(c, 1, self.burst_max).astype(jnp.int32)

    def chunk(self, t0: int, t1: int) -> tuple[jax.Array, jax.Array]:
        w = self._base().w_true()
        c = self.counts(t0, t1)                                # (T, m)
        keys = round_keys(jax.random.PRNGKey(self.seed + 1), t0, t1)
        total = jnp.zeros((t1 - t0, self.nodes, self.n), jnp.float32)
        # burst_max is small and static: unrolled accumulation keeps memory
        # at one (T, m, n) buffer instead of a (T, m, burst_max, n) stack
        for k in range(self.burst_max):
            sample = jax.vmap(
                lambda kk: jax.random.normal(
                    jax.random.fold_in(kk, k), (self.nodes, self.n))
            )(keys)
            total = total + jnp.where((k < c)[:, :, None], sample, 0.0)
        x = total / c[:, :, None] / jnp.sqrt(self.n)
        y = labels_from_logits(jnp.einsum("n,tmn->tm", w, x,
                                          precision=LABEL_PRECISION))
        return x.astype(jnp.float32), y.astype(jnp.float32)

    def chunks(self, chunk_rounds: int = 512):
        return _chunks(self, chunk_rounds)


@STREAMS.register("social_sparse")
def _social(n: int, nodes: int, rounds: int, seed: int = 0,
            sparsity_true: float = 0.05, label_noise: float = 0.0) -> Stream:
    return SocialStream(n=n, nodes=nodes, rounds=rounds, seed=seed,
                        sparsity_true=sparsity_true, label_noise=label_noise)


@STREAMS.register("drift")
def _drift(n: int, nodes: int, rounds: int, seed: int = 0,
           period: int = 64, mode: str = "reshuffle",
           sparsity_true: float = 0.05, label_noise: float = 0.0) -> Stream:
    return DriftStream(n=n, nodes=nodes, rounds=rounds, seed=seed,
                       period=period, mode=mode,
                       sparsity_true=sparsity_true, label_noise=label_noise)


@STREAMS.register("heterogeneous")
def _het(n: int, nodes: int, rounds: int, seed: int = 0,
         scale_spread: float = 0.5, noise_max: float = 0.2,
         sparsity_true: float = 0.05) -> Stream:
    return HeterogeneousStream(n=n, nodes=nodes, rounds=rounds, seed=seed,
                               scale_spread=scale_spread, noise_max=noise_max,
                               sparsity_true=sparsity_true)


@STREAMS.register("bursty")
def _bursty(n: int, nodes: int, rounds: int, seed: int = 0,
            burst_max: int = 8, tail: float = 1.5,
            sparsity_true: float = 0.05) -> Stream:
    return BurstyStream(n=n, nodes=nodes, rounds=rounds, seed=seed,
                        burst_max=burst_max, tail=tail,
                        sparsity_true=sparsity_true)
