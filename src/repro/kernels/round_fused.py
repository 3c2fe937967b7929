"""Pallas TPU kernels: the WHOLE per-round body in two fused passes.

The paper's round (Algorithm 1 steps 6-11) is clip -> Laplace-noise ->
gossip-mix -> sparse-OMD update -> L1 prox over an (m, n) parameter block
with n = 1e4..1e8. The seed kernel (`pdomd_update`) fused the last three
steps for a ring only; these kernels cover the full chain for ANY fixed
topology (general `SparseGraph` degree via its dense (m, m) form) in two
passes, chosen because the clip factor needs each node's FULL-row gradient
norm — a reduction a single streaming pass over n-blocks cannot both
produce and consume:

``round_stats`` (pass 1) streams theta and x once and accumulates every
per-node reduction the round needs, with the prox fused in so w is never
materialized:

    w        = soft_threshold(theta, lam_t)          (or identity)
    dot_i    = sum_j w_ij x_ij          -> margin, loss, correct, active
    xsq_i    = sum_j x_ij^2             -> clip factor (see below)
    nnz_i    = sum_j [w_ij != 0]        -> sparsity
    wsum_j   = sum_i w_ij               -> w_bar (sharded path: psum'd)
    wbdot_i  = sum_j (wsum_j / m) x_ij  -> w_bar hinge loss (unsharded)

The hinge gradient is rank-1 per node (g_i = -[margin_i < 1] y_i x_i), so
its L2 norm is active_i * ||x_i|| and the whole clip collapses to an (m,)
coefficient computed from ``xsq`` on the host side — no gradient matrix is
ever built.

``round_update`` (pass 2) streams theta, delta, x (and the stale recv block
when delayed) once, with the dense mixing matrix A resident in VMEM across
the whole pass, and applies the unified mixing algebra of
`repro.api.mixers.MixerBase`:

    tilde = theta + delta                     (noise-add; delta sampled
                                               OUTSIDE with the engines'
                                               exact jax.random calls)
    recv  = tilde            (synchronous)  |  ring slot (delayed)
    s     = tilde (noise_self) | theta
    mixed = A @ recv + diag(A) * (s - recv)   (k-neighbor mix, MXU)
    next  = mixed - alpha_t * coeff * x       (OMD dual step, clip folded
                                               into coeff)
    next  = alive ? next : theta              (fault crash freeze)

``node_sum`` reduces the (m,) w_bar hinge terms in one kernel, so the
cross-node mean rounds the same under any seed batch.

Unfused, the round body is ~7 HBM round-trips over the (m, n) state; fused
it is 3 reads + 1 write for the update pass plus the stats pass — the
memory-bound win `repro.obs.cost` rooflines in BENCH_kernels.json.

Tiling: n is zero-padded to a LANE (128) multiple and the grid walks
column blocks of up to ``block_cols`` lanes; m is zero-padded to a SUBLANE
(8) multiple and stays fully resident. Each kernel's block width comes
from `col_block`, which holds the kernel's scoped VMEM (`vmem_bytes`:
double-buffered streamed blocks, body temporaries, the resident dense A)
under `VMEM_LIMIT_BYTES`; a shape no width can fit raises ValueError
before lowering. Zero-padded rows/columns are provably inert: w and x are
zero there, so every reduction and the update leave them zero. The TPU
grid is sequential, so pass 1 accumulates its reductions into re-visited
output blocks (`@pl.when(j == 0)` zero-init, as in `kernels/hinge_grad`).
On the CPU the kernels run with ``interpret=True``, so tests check the
real kernel bodies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
DEFAULT_BLOCK_COLS = 512
# The scoped-VMEM limit Mosaic gives one kernel by default on a TPU v5e. It
# is passed to every pallas_call explicitly, so the budget that `col_block`
# checks and the limit the compiler enforces are the same number.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024
KERNELS = ("round_stats", "round_update", "dual_step")


def _pad_cols(n: int) -> int:
    return -(-n // LANE) * LANE


def _pad_rows(m: int) -> int:
    return -(-m // SUBLANE) * SUBLANE


def vmem_bytes(kernel: str, m_pad: int, block: int) -> int:
    """Upper bound on one kernel's scoped VMEM at an (m_pad, block) grid step.

    ``blk`` is one streamed f32 (m_pad, block) tile; the Pallas pipeline
    double-buffers every streamed operand, and the kernel body keeps a few
    block-sized temporaries. ``row`` is the (m_pad, 4) per-node table, which
    VMEM pads to 128 lanes. The coefficients bound what the v5e compiler
    accepts under VMEM_LIMIT_BYTES, found by compiling each kernel at
    blocks of 128..2048 lanes and every m_pad the budget admits (steps of
    8 rows for round_update, 16 for the others); the boundary cases are
    tests/test_tpu_compile.py:

      round_stats   2 streamed inputs x 2 buffers + temporaries  -> 8 blk
      dual_step     3 inputs + 1 output x 2 buffers + temps      -> 10 blk
      round_update  4 inputs + 2 outputs x 2 buffers + temps     -> 21 blk,
                    plus the resident A (lane-padded) and the float32
                    matmul's scratch, which grows with A times the block
                    width: a * (2 * block / 128 - 1) in all
    """
    blk = m_pad * block * 4
    row = m_pad * LANE * 4
    if kernel == "round_stats":
        return 8 * blk + 2 * row
    if kernel == "dual_step":
        return 10 * blk + 2 * row
    if kernel == "round_update":
        a = m_pad * _pad_cols(m_pad) * 4
        return a * (2 * block // LANE - 1) + 21 * blk + 2 * row
    raise ValueError(f"unknown kernel {kernel!r}; expected one of {KERNELS}")


def col_block(kernel: str, m_pad: int, n_pad: int, block_cols: int) -> int:
    """Widest LANE multiple <= block_cols that divides n_pad and keeps
    ``kernel`` within VMEM_LIMIT_BYTES; ValueError when none does."""
    b = max(LANE, min(block_cols, n_pad) // LANE * LANE)
    while b >= LANE:
        if n_pad % b == 0 and vmem_bytes(kernel, m_pad, b) <= VMEM_LIMIT_BYTES:
            return b
        b -= LANE
    need = vmem_bytes(kernel, m_pad, LANE)
    raise ValueError(
        f"{kernel} at (m_pad={m_pad}, n_pad={n_pad}) needs "
        f"{need / 2**20:.2f} MiB of VMEM even at {LANE}-lane blocks, over "
        f"the {VMEM_LIMIT_BYTES / 2**20:.0f} MiB scoped VMEM limit "
        f"(VMEM_LIMIT_BYTES); fewer nodes per device fit")


_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


# ---------------------------------------------------------------------------
# pass 1: per-node reductions (prox fused, w never materialized)
# ---------------------------------------------------------------------------

def _stats_kernel(theta_ref, x_ref, scal_ref,
                  dot_ref, xsq_ref, nnz_ref, wbdot_ref, wsum_ref):
    """scal_ref (1, 4): [lam_t, m_real, prox_is_l1, 0]."""
    j = pl.program_id(0)
    lam_t = scal_ref[0, 0]
    m_real = scal_ref[0, 1]
    prox_l1 = scal_ref[0, 2]

    theta = theta_ref[...]
    x = x_ref[...]
    soft = jnp.sign(theta) * jnp.maximum(jnp.abs(theta) - lam_t, 0.0)
    w = jnp.where(prox_l1 > 0, soft, theta)

    @pl.when(j == 0)
    def _init():
        dot_ref[...] = jnp.zeros_like(dot_ref)
        xsq_ref[...] = jnp.zeros_like(xsq_ref)
        nnz_ref[...] = jnp.zeros_like(nnz_ref)
        wbdot_ref[...] = jnp.zeros_like(wbdot_ref)

    # per-node partial reductions over this column block; (m, 1) keepdims
    # broadcast across the LANE-wide output block so the layout stays tiled
    dot_ref[...] += jnp.sum(w * x, axis=1, keepdims=True)
    xsq_ref[...] += jnp.sum(x * x, axis=1, keepdims=True)
    nnz_ref[...] += jnp.sum((w != 0.0).astype(jnp.float32), axis=1,
                            keepdims=True)
    # w_bar restricted to this block: padding rows hold w == 0, so the raw
    # column sum over m_pad rows equals the sum over the m real rows
    wsum = jnp.sum(w, axis=0, keepdims=True)                # (1, B)
    wsum_ref[...] = jnp.broadcast_to(wsum, wsum_ref.shape)
    wbdot_ref[...] += jnp.sum((wsum / m_real) * x, axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("prox_l1", "block_cols", "interpret"))
def round_stats(theta: jax.Array, x: jax.Array, lam_t: jax.Array,
                m_real: int, *, prox_l1: bool = True,
                block_cols: int = DEFAULT_BLOCK_COLS,
                interpret: bool = False):
    """Per-node round statistics in one streamed pass over (m_pad, n_pad).

    Returns ``(dot, xsq, nnz, wbdot, wsum)`` — the first four (m_pad,)
    per-node reductions, ``wsum`` the (n_pad,) column sums of w. ``wbdot``
    is only meaningful when all m rows are resident (the unsharded path);
    the node-sharded path psums ``wsum`` across shards instead.
    """
    m_pad, n_pad = theta.shape
    if n_pad % LANE or m_pad % SUBLANE:
        raise ValueError(f"round_stats needs (8k, 128k) padded input, got "
                         f"{theta.shape}")
    B = col_block("round_stats", m_pad, n_pad, block_cols)
    grid = (n_pad // B,)
    blk = pl.BlockSpec((m_pad, B), lambda j: (0, j))
    red = pl.BlockSpec((m_pad, LANE), lambda j: (0, 0))
    scal = jnp.stack([jnp.asarray(lam_t, jnp.float32),
                      jnp.asarray(m_real, jnp.float32),
                      jnp.asarray(1.0 if prox_l1 else 0.0, jnp.float32),
                      jnp.zeros((), jnp.float32)]).reshape(1, 4)
    dot, xsq, nnz, wbdot, wsum = pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[blk, blk, pl.BlockSpec((1, 4), lambda j: (0, 0))],
        out_specs=[red, red, red, red,
                   pl.BlockSpec((SUBLANE, B), lambda j: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((m_pad, LANE), jnp.float32)] * 4
        + [jax.ShapeDtypeStruct((SUBLANE, n_pad), jnp.float32)],
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(theta.astype(jnp.float32), x.astype(jnp.float32), scal)
    return dot[:, 0], xsq[:, 0], nnz[:, 0], wbdot[:, 0], wsum[0]


# ---------------------------------------------------------------------------
# pass 2: noise-add + dense gossip mix + OMD dual step (+ crash freeze)
# ---------------------------------------------------------------------------

def _update_kernel(a_ref, theta_ref, delta_ref, x_ref, recv_ref,
                   pernode_ref, scal_ref, out_ref, tilde_ref):
    """pernode_ref (m_pad, 4): [coeff, diag, alive, 0] columns.
    scal_ref (1, 4): [alpha_t, use_recv, noise_self, 0]."""
    alpha = scal_ref[0, 0]
    use_recv = scal_ref[0, 1]
    noise_self = scal_ref[0, 2]
    coeff = pernode_ref[:, 0:1]
    diag = pernode_ref[:, 1:2]
    alive = pernode_ref[:, 2:3]

    theta = theta_ref[...]
    tilde = theta + delta_ref[...]
    recv = jnp.where(use_recv > 0, recv_ref[...], tilde)
    s = jnp.where(noise_self > 0, tilde, theta)
    # HIGHEST pins Mosaic to a float32 contraction; its default may take
    # bf16 passes, which would break the float32 contract of docs/kernels.md
    mixed = jnp.dot(a_ref[...], recv, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32) + diag * (s - recv)
    nxt = mixed - alpha * (coeff * x_ref[...])
    out_ref[...] = jnp.where(alive > 0, nxt, theta)
    tilde_ref[...] = tilde


@functools.partial(jax.jit, static_argnames=("noise_self", "block_cols",
                                             "interpret"))
def round_update(A: jax.Array, theta: jax.Array, delta: jax.Array,
                 x: jax.Array, recv: jax.Array, coeff: jax.Array,
                 diag: jax.Array, alive: jax.Array, alpha_t: jax.Array,
                 use_recv: jax.Array, noise_self: bool, *,
                 block_cols: int = DEFAULT_BLOCK_COLS,
                 interpret: bool = False):
    """Fused noise-add + mix + dual step. Returns (theta_next, tilde).

    ``A`` (m_pad, m_pad) dense doubly-stochastic weights (zero-padded);
    ``recv`` the stale broadcast block when ``use_recv`` (traced bool as
    f32) is set, ignored otherwise; ``coeff`` the clipped hinge coefficient
    (grad = coeff * x); ``alive`` 1.0 except on fault-frozen rows.
    """
    m_pad, n_pad = theta.shape
    if n_pad % LANE or m_pad % SUBLANE:
        raise ValueError(f"round_update needs (8k, 128k) padded input, got "
                         f"{theta.shape}")
    if A.shape != (m_pad, m_pad):
        raise ValueError(f"A must be ({m_pad}, {m_pad}), got {A.shape}")
    B = col_block("round_update", m_pad, n_pad, block_cols)
    grid = (n_pad // B,)
    blk = pl.BlockSpec((m_pad, B), lambda j: (0, j))
    pernode = jnp.stack([
        coeff.astype(jnp.float32), diag.astype(jnp.float32),
        alive.astype(jnp.float32), jnp.zeros_like(coeff, jnp.float32)],
        axis=1)
    scal = jnp.stack([jnp.asarray(alpha_t, jnp.float32),
                      jnp.asarray(use_recv, jnp.float32),
                      jnp.asarray(1.0 if noise_self else 0.0, jnp.float32),
                      jnp.zeros((), jnp.float32)]).reshape(1, 4)
    theta_next, tilde = pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((m_pad, m_pad), lambda j: (0, 0)),
                  blk, blk, blk, blk,
                  pl.BlockSpec((m_pad, 4), lambda j: (0, 0)),
                  pl.BlockSpec((1, 4), lambda j: (0, 0))],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32)] * 2,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(A.astype(jnp.float32), theta.astype(jnp.float32),
      delta.astype(jnp.float32), x.astype(jnp.float32),
      recv.astype(jnp.float32), pernode, scal)
    return theta_next, tilde


# ---------------------------------------------------------------------------
# cross-node sum in one fixed order
# ---------------------------------------------------------------------------

def _sum_kernel(v_ref, out_ref):
    out_ref[...] = jnp.broadcast_to(jnp.sum(v_ref[...]), out_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def node_sum(v: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Sum of an (m,) per-node vector, reduced inside one kernel.

    XLA orders a reduction by the shape it sees, so the same (m,) sum can
    round differently in a vmap over S seeds on one device and over S/D
    seeds per device. The kernel reduces each vector the same way under
    any batch, which keeps `run_batch`'s seed-sharded and vmapped results
    equal to the bit on a TPU.
    """
    m = v.shape[0]
    width = -(-m // (SUBLANE * LANE)) * SUBLANE * LANE
    tile = jnp.pad(v.astype(jnp.float32), (0, width - m)).reshape(SUBLANE, -1)
    out = pl.pallas_call(
        _sum_kernel,
        out_shape=jax.ShapeDtypeStruct((SUBLANE, LANE), jnp.float32),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(tile)
    return out[0, 0]


def _dual_kernel(mixed_ref, x_ref, theta_ref, pernode_ref, scal_ref, out_ref):
    alpha = scal_ref[0, 0]
    coeff = pernode_ref[:, 0:1]
    alive = pernode_ref[:, 2:3]
    nxt = mixed_ref[...] - alpha * (coeff * x_ref[...])
    out_ref[...] = jnp.where(alive > 0, nxt, theta_ref[...])


@functools.partial(jax.jit, static_argnames=("block_cols", "interpret"))
def dual_step(mixed: jax.Array, x: jax.Array, theta: jax.Array,
              coeff: jax.Array, alive: jax.Array, alpha_t: jax.Array, *,
              block_cols: int = DEFAULT_BLOCK_COLS,
              interpret: bool = False) -> jax.Array:
    """Hybrid-path pass 2: OMD dual step + crash freeze, mixing already done
    in XLA (any mixer — faults, heterogeneous delays, time-varying A(t))."""
    m_pad, n_pad = mixed.shape
    if n_pad % LANE or m_pad % SUBLANE:
        raise ValueError(f"dual_step needs (8k, 128k) padded input, got "
                         f"{mixed.shape}")
    B = col_block("dual_step", m_pad, n_pad, block_cols)
    grid = (n_pad // B,)
    blk = pl.BlockSpec((m_pad, B), lambda j: (0, j))
    pernode = jnp.stack([
        coeff.astype(jnp.float32), jnp.zeros_like(coeff, jnp.float32),
        alive.astype(jnp.float32), jnp.zeros_like(coeff, jnp.float32)],
        axis=1)
    scal = jnp.stack([jnp.asarray(alpha_t, jnp.float32)] +
                     [jnp.zeros((), jnp.float32)] * 3).reshape(1, 4)
    return pl.pallas_call(
        _dual_kernel,
        grid=grid,
        in_specs=[blk, blk, blk,
                  pl.BlockSpec((m_pad, 4), lambda j: (0, 0)),
                  pl.BlockSpec((1, 4), lambda j: (0, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
    )(mixed.astype(jnp.float32), x.astype(jnp.float32),
      theta.astype(jnp.float32), pernode, scal)
