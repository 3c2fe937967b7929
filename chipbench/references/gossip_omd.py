"""Plain reference of the paper's Algorithm 1 (arXiv:1602.06489 §II-D).

Straight float32 `jax.numpy`, written from the paper and the configuration
file alone: it imports nothing of the program and takes nothing the program
made. One round, for m nodes with dual state theta (m, n):

    alpha_t = alpha0 / sqrt(t)                 ("sqrt_t", t 1-based)
    lam_t   = alpha_t * lam
    w       = sign(theta) * max(|theta| - lam_t, 0)          L1 prox
    margin  = y * <w_i, x_i>;  loss = max(1 - margin, 0)     hinge
    correct = [sign(<w_i, x_i>) == y_i]
    g       = -[margin < 1] * y * x;  g *= min(1, L / ||g_i||)   clip
    key, sub = split(key);  noise = Laplace(scale) from uniform(sub, (m, n))
    scale   = 2 alpha_t L / eps       (the "coordinate" calibration)
    tilde   = theta + noise
    mixed   = A tilde;  A = ring: self weight s, each neighbour (1 - s) / 2
    theta'  = mixed - alpha_t * g                            OMD dual step
    w_bar_loss = mean_i max(1 - y_i <mean_j w_j, x_i>, 0)
    sparsity   = share of exact zeros in w

The key stream is the documented one: the state's key starts as
``PRNGKey(seed)`` and each round splits it once; the Laplace sample is the
inverse CDF -sign(u) log1p(-2|u|) of u ~ U(-1/2 + 1e-7, 1/2).

``precision`` is how the reference computes:

  highest   float32, every contraction at HIGHEST (the reference itself);
  high      float32, every contraction in three bf16 passes (hi*hi + hi*lo
            + lo*hi, the TPU's "high"), emulated so that it reads the same
            on any backend;
  bfloat16  state, data and noise held and computed in bfloat16.

``mix`` is "dense" (A as an (m, m) matrix, contracted like any other) or
"roll" (the same ring as two shifts, for node counts whose A cannot be held).

The hinge is the round's one discontinuity: where a margin lies within
rounding of 1, two float32 computations may take opposite sides of it, and
the learners part from there. As the reference of a served model is run over
the tokens that were served, ``run(..., follow=losses)`` takes the side that
the compared run took, read from that run's own per-round losses (loss > 0
exactly when margin < 1), and computes everything else itself. A wrong side
is a wrong loss, which the comparison of losses sees.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "bfloat16")
HI = jax.lax.Precision.HIGHEST

# The settings of a configuration's ``spec`` that the reference covers, and
# the values it covers of each. Any other setting is refused; the keys of
# NEUTRAL change how the program runs, not what it computes.
COVERED = {"mixer": ("ring",), "mechanism": ("laplace",),
           "calibration": ("coordinate",), "schedule": ("sqrt_t",)}
NUMBERS = ("nodes", "dim", "eps", "clip_norm", "alpha0", "lam",
           "mixer_options")
NEUTRAL = ("horizon", "backend", "backend_options")


def check_spec(spec: dict) -> None:
    """Refuse a spec that sets anything the reference does not compute."""
    for key, values in COVERED.items():
        if spec.get(key) not in values:
            raise ValueError(f"the reference covers {key} in {values}, not "
                             f"{spec.get(key)!r}")
    unknown = set(spec) - set(COVERED) - set(NUMBERS) - set(NEUTRAL)
    if unknown:
        raise ValueError(f"the reference does not cover {sorted(unknown)}")
    if set(spec["mixer_options"]) != {"self_weight"}:
        raise ValueError("the reference's ring takes mixer_options "
                         "{'self_weight'} only")


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _contract(spec: str, a, b, precision: str):
    if precision != "high":
        return jnp.einsum(spec, a, b, precision=HI).astype(a.dtype)
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return (jnp.einsum(spec, ah, bh, precision=HI)
            + jnp.einsum(spec, ah, bl, precision=HI)
            + jnp.einsum(spec, al, bh, precision=HI))


def ring_matrix(m: int, self_weight: float) -> np.ndarray:
    nw = (1.0 - self_weight) / 2.0
    a = np.zeros((m, m), np.float32)
    i = np.arange(m)
    a[i, i] += self_weight
    a[i, (i + 1) % m] += nw
    a[i, (i - 1) % m] += nw
    return a


class Reference:
    """The learner of one configuration, round by round."""

    def __init__(self, config: dict, precision: str = "highest",
                 sharding=None):
        if precision not in PRECISIONS:
            raise ValueError(f"precision {precision!r}: one of {PRECISIONS}")
        ref, spec = config.get("reference", {}), config["spec"]
        check_spec(spec)
        self.m, self.n = int(spec["nodes"]), int(spec["dim"])
        self.eps = float(spec["eps"])
        self.clip = float(spec["clip_norm"])
        self.alpha0 = float(spec["alpha0"])
        self.lam = float(spec["lam"])
        self.self_weight = float(spec["mixer_options"]["self_weight"])
        self.mix = ref.get("mix", "roll")
        self.precision = precision
        self.dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
        self.sharding = sharding
        self._chunk = jax.jit(self._chunk_fn)

    # -- one round -----------------------------------------------------------

    def _alpha(self, t):
        return self.alpha0 / jnp.sqrt(jnp.maximum(t.astype(jnp.float32), 1.0))

    def _constrain(self, a):
        if self.sharding is None:
            return a
        return jax.lax.with_sharding_constraint(a, self.sharding)

    def _mix(self, tilde):
        if self.mix == "dense":
            a = jnp.asarray(ring_matrix(self.m, self.self_weight), self.dtype)
            return _contract("ij,jn->in", a, tilde, self.precision)
        nw = (1.0 - self.self_weight) / 2.0
        return (self.self_weight * tilde + nw * jnp.roll(tilde, 1, axis=0)
                + nw * jnp.roll(tilde, -1, axis=0))

    def _round(self, carry, batch):
        theta, key, t = carry                     # t: rounds done so far
        x, y, follow = batch
        x = self._constrain(x.astype(self.dtype))
        y = y.astype(self.dtype)
        dt = self.dtype
        alpha = self._alpha(t + 1)
        lam_t = (alpha * self.lam).astype(dt)

        w = jnp.sign(theta) * jnp.maximum(jnp.abs(theta) - lam_t, 0.0)
        dot = _contract("mn,mn->m", w, x, self.precision)
        margin = y * dot
        loss = jnp.maximum(1.0 - margin, 0.0)
        correct = (jnp.sign(dot) == y).astype(jnp.float32)
        active = jnp.where(jnp.isnan(follow), margin < 1.0,
                           follow > 0).astype(dt)
        g = -(active * y)[:, None] * x
        norm = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)), axis=1))
        factor = jnp.minimum(1.0, self.clip / jnp.maximum(norm, 1e-12))
        g = g * factor.astype(dt)[:, None]

        key, sub = jax.random.split(key)
        scale = 2.0 * alpha * self.clip / self.eps
        u = jax.random.uniform(sub, (self.m, self.n), jnp.float32,
                               minval=-0.5 + 1e-7, maxval=0.5)
        noise = scale * (-jnp.sign(u) * jnp.log1p(-2.0 * jnp.abs(u)))
        tilde = self._constrain(theta + noise.astype(dt))
        theta_next = self._constrain(self._mix(tilde) - alpha.astype(dt) * g)

        w_bar = jnp.mean(w.astype(jnp.float32), axis=0).astype(dt)
        wb_margin = y * _contract("mn,n->m", x, w_bar, self.precision)
        out = {"loss": loss.astype(jnp.float32),
               "correct": correct,
               "w_bar_loss": jnp.mean(jnp.maximum(
                   1.0 - wb_margin.astype(jnp.float32), 0.0)),
               "sparsity": jnp.mean((w == 0).astype(jnp.float32))}
        return (theta_next, key, t + 1), out

    def _chunk_fn(self, carry, xs, ys, follow):
        return jax.lax.scan(self._round, carry, (xs, ys, follow))

    # -- driving -------------------------------------------------------------

    def init(self, seed: int):
        theta = jnp.zeros((self.m, self.n), self.dtype)
        if self.sharding is not None:
            theta = jax.device_put(theta, self.sharding)
        key = jax.random.PRNGKey(int(seed) % 2**32)
        return theta, key, jnp.zeros((), jnp.int32)

    def run(self, seed: int, chunks, follow=None) -> dict:
        """Follow the chunks ((xs, ys) pairs, in order) from the seed.

        ``follow``, when given, is the compared run's per-round losses
        (R, m) over the same rounds: the hinge takes the side they show.
        Returns per-round ``loss`` (R, m), ``correct`` (R, m), ``w_bar_loss``
        (R,), ``sparsity`` (R,) as NumPy arrays, and ``w`` (m, n), the primal
        after the last round, still on the device."""
        carry = self.init(seed)
        outs, done = [], 0
        for xs, ys in chunks:
            rounds = xs.shape[0]
            if follow is None:
                f = np.full((rounds, self.m), np.nan, np.float32)
            else:
                f = np.asarray(follow[done:done + rounds], np.float32)
            carry, out = self._chunk(carry, xs, ys, f)
            outs.append(jax.device_get(out))
            done += rounds
        theta, _, t = carry
        result = {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}
        result["w"] = _primal_jit(theta, t, self.alpha0, self.lam)
        return result


@functools.partial(jax.jit, static_argnums=(2, 3))
def _primal_jit(theta, t, alpha0, lam):
    alpha = alpha0 / jnp.sqrt(jnp.maximum(t.astype(jnp.float32), 1.0))
    theta = theta.astype(jnp.float32)
    return jnp.sign(theta) * jnp.maximum(jnp.abs(theta) - alpha * lam, 0.0)


def eps_ledger(eps: float, rounds: int, disjoint: bool = False) -> np.ndarray:
    """The cumulative guarantee after each of ``rounds`` rounds. A stream
    whose rounds touch disjoint rows composes in parallel (the paper's
    Theorem 1): eps after every round. One that repeats rows, as a replayed
    pool does, composes sequentially: eps * t."""
    if disjoint:
        return np.full(rounds, eps, np.float64)
    return eps * np.arange(1, rounds + 1, dtype=np.float64)
