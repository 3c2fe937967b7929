"""The one traffic generator: the paper's social_sparse stream, replayed from
a pool made at set-up or generated chunk by chunk as it arrives.

The data is the benchmark's own copy of the §V workload (a fixed sparse w*,
gaussian features scaled by 1/sqrt(n), labels sign(<w*, x>) with optional
flips), keyed per absolute round so that a round's data never depends on how
the horizon is cut into chunks. It is kept here, and not imported from the
program, so that neither the data nor the reference moves when the program
changes. The seed is a traced argument: every seed runs the same compiled
programs.

A traffic mix is a JSON file of parameters (``chipbench/traffic/<name>.json``)
that `Traffic` reads: the ``mode``, the ground truth's ``sparsity_true`` and
the ``label_noise``, and for a replay the pool's ``pool_chunks``.

  replay  a pool of ``pool_chunks`` chunks is made on the device at set-up,
          in one jitted call from the seed; chunk k of a run is pool chunk
          k mod pool_chunks, handed over as it is (no copy, no program), so
          the window runs nothing but the chunk program. The pool repeats
          rows, so the stream declares itself not disjoint and the privacy
          accountant composes sequentially.
  stream  w* is made at set-up and the generator's program compiled there;
          chunk k is made on the device when the runner asks for it, by one
          call of that program (``STREAM_PROGRAM`` in a trace) for rounds
          [k c, (k + 1) c): the same rows as pool chunk k of a replay, bit
          for bit. Every round's rows are fresh, so the stream declares
          itself disjoint and the accountant composes in parallel (flat
          eps, the paper's Theorem 1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Labels are the sign of a float32 contraction, pinned to HIGHEST so that
# they are a function of the seed alone and not of the ambient precision.
LABEL_PRECISION = jax.lax.Precision.HIGHEST
MODES = ("replay", "stream")
STREAM_PROGRAM = "jit_stream_chunk"     # the stream's generator, in a trace


def seed_array(seed: int) -> jax.Array:
    """The seed as the uint32 that `jax.random.PRNGKey(seed)` keys on."""
    return jnp.asarray(int(seed) % 2**32, jnp.uint32)


def w_true(n: int, sparsity_true: float, seed) -> jax.Array:
    """The sparse ground truth w* (n,), unit L2 norm."""
    kw, km = jax.random.split(jax.random.PRNGKey(seed))
    mask = jax.random.uniform(km, (n,)) < sparsity_true
    w = jax.random.normal(kw, (n,)) * mask
    return (w / jnp.maximum(jnp.linalg.norm(w), 1e-9)).astype(jnp.float32)


def rounds(w: jax.Array, nodes: int, seed, t0, count: int,
           label_noise: float = 0.0) -> tuple[jax.Array, jax.Array]:
    """Rounds [t0, t0 + count) of the stream: xs (count, nodes, n), ys
    (count, nodes) in {-1, +1}. ``seed`` and ``t0`` may be traced."""
    n = w.shape[0]
    base = jax.random.PRNGKey(seed + 1)
    ts = t0 + jnp.arange(count, dtype=jnp.int32)
    keys = jax.vmap(lambda t: jax.random.fold_in(base, t))(ts)
    kx, kn = jax.vmap(lambda k: tuple(jax.random.split(k)))(keys)
    x = jax.vmap(lambda k: jax.random.normal(k, (nodes, n)))(kx) / jnp.sqrt(n)
    logits = jnp.einsum("n,tmn->tm", w, x, precision=LABEL_PRECISION)
    y = jnp.where(logits >= 0, 1.0, -1.0)
    if label_noise > 0:
        flip = jax.vmap(lambda k: jax.random.uniform(k, (nodes,)))(kn)
        y = jnp.where(flip < label_noise, -y, y)
    return x.astype(jnp.float32), y.astype(jnp.float32)


class Traffic:
    """A `repro.api` Stream over one traffic mix, for one configuration.

    ``shardings`` is an (xs, ys) pair that places every chunk (for a
    node-sharded cell, the program's own ``P(None, "node")`` data layout, so
    that no chunk moves between chips), or None for the default device.
    ``annotate`` wraps each ``chunk`` call in a profiler annotation.
    """

    def __init__(self, mix: dict, *, n: int, nodes: int, chunk_rounds: int,
                 horizon: int, seed: int, shardings=None, annotate=None):
        if mix.get("mode") not in MODES:
            raise ValueError(f"traffic mode {mix.get('mode')!r}: one of "
                             f"{MODES}")
        self.mode = mix["mode"]
        self.disjoint = self.mode == "stream"   # a pool repeats its rows
        self.n, self.nodes, self.rounds = n, nodes, horizon
        self.chunk_rounds = chunk_rounds
        self.sparsity_true = float(mix.get("sparsity_true", 0.05))
        self.label_noise = float(mix.get("label_noise", 0.0))
        self.pool_chunks = int(mix.get("pool_chunks", 1))
        self._seed = seed_array(seed)
        self._annotate = annotate
        self._shardings = shardings
        self._pool = self._w = self._generate = None

    def prepare(self) -> None:
        """Set-up: make the pool on the device, in one jitted call; or make
        w* and compile the stream's generator."""
        if self.mode == "stream":
            self._prepare_stream()
            return
        chunks, c = self.pool_chunks, self.chunk_rounds
        out = None if self._shardings is None else (self._shardings,) * chunks

        def make(seed):
            w = w_true(self.n, self.sparsity_true, seed)
            return tuple(rounds(w, self.nodes, seed, k * c, c,
                                self.label_noise) for k in range(chunks))
        self._pool = jax.block_until_ready(
            jax.jit(make, out_shardings=out)(self._seed))

    def _prepare_stream(self) -> None:
        nodes, c, noise = self.nodes, self.chunk_rounds, self.label_noise

        def stream_chunk(w, seed, t0):
            return rounds(w, nodes, seed, t0, c, noise)
        self._w = jax.block_until_ready(jax.jit(
            w_true, static_argnums=(0, 1))(self.n, self.sparsity_true,
                                           self._seed))
        self._generate = jax.jit(stream_chunk, out_shardings=self._shardings)
        jax.block_until_ready(self.chunk_data(0))

    def release(self) -> None:
        """Drop the pool or w*, so that their device memory can be freed."""
        self._pool = self._w = None

    def chunk(self, t0: int, t1: int) -> tuple[jax.Array, jax.Array]:
        """Rounds [t0, t1): one aligned chunk (the `repro.api` Stream call)."""
        c = self.chunk_rounds
        if t1 - t0 != c or t0 % c:
            raise ValueError(f"chunk [{t0}, {t1}) is not one aligned "
                             f"{c}-round chunk")
        if self._annotate is None:
            return self.chunk_data(t0 // c)
        with self._annotate("chipbench.stream"):
            return self.chunk_data(t0 // c)

    def chunk_data(self, k: int) -> tuple[jax.Array, jax.Array]:
        """The data of the run's k-th chunk (0-based)."""
        if self.mode == "stream":
            return self._generate(self._w, self._seed,
                                  np.int32(k * self.chunk_rounds))
        return self._pool[k % self.pool_chunks]
