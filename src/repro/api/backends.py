"""BACKENDS — how a RunSpec's round body executes (reference XLA or Pallas).

`RunSpec.backend` selects the execution backend by name (BACKENDS registry)
and `backend_options` configure it; `repro.api.runner.make_chunk_program`
resolves the backend inside the chunk builders, so streams, delay rings,
faults, checkpoints, serving snapshots and telemetry compose with either
backend unchanged:

  "reference" — the engines as built by `RunSpec.build_simulator` /
                `build_distributed`: plain XLA, the correctness oracle every
                other backend is measured against.
  "pallas"    — the fused fast path (`repro.kernels.round_fused`): the
                whole round body — prox + per-node stats, clip (folded into
                a rank-1 coefficient), noise-add, k-neighbor gossip mix over
                the dense form of any fixed `SparseGraph` topology, OMD dual
                step and crash freeze — in two Pallas kernels with per-node
                parameter blocks resident in VMEM across the round. Runs
                under ``interpret=True`` on the CPU (the tests check the
                real kernel bodies) and compiles to Mosaic on a TPU.

The pallas backend keeps the engines' state pytrees (`SimState` /
`GossipState`), their PRNG stream (noise is sampled OUTSIDE the kernels
with the exact `jax.random` calls of the reference round, so the Laplace
draws are bit-identical) and their chunk scan, so checkpoints, snapshots
and `run_batch`'s seed vmap interchange with the reference backend. The
iterates themselves agree to the float32 tolerance contract documented in
docs/kernels.md (kernel reduction order differs from XLA's).

Two execution modes, picked per spec (``backend_options={"mode": ...}``):

  fused  — mixing happens INSIDE the update kernel via the dense (m, m)
           matrix of the spec's fixed topology (any `SparseGraph` degree);
           the dense block must sit in VMEM next to the streamed operands
           (`repro.kernels.round_fused.vmem_bytes`).
  hybrid — mixing stays in XLA (`mixer.mix` / `mix_history` — any mixer:
           faults, per-edge heterogeneous delays, time-varying schedules)
           between the stats kernel and a smaller fused dual-step kernel.

``mode="auto"`` (default) fuses when the resolved mixer lowers to a fixed
sparse graph and the update kernel fits the VMEM budget, else falls back
to hybrid; a node count whose stats or dual-step kernel fits no block
width raises ValueError before anything is lowered. The node-sharded path
(`repro.api.shard_node`) always runs hybrid per shard: its ppermute halo
exchange stays outside the kernels by design.

>>> from repro.api import BACKENDS, RunSpec, run, ExecConfig
>>> sorted(BACKENDS.names())
['pallas', 'reference']
>>> spec = RunSpec(nodes=4, dim=128, horizon=4, eps=1.0, alpha0=0.5,
...                lam=0.01, stream="drift", backend="pallas")
>>> res = run(spec, engine="sim",
...           exec=ExecConfig(compute_regret=False, warmup=False))
>>> res.rounds
4
>>> BACKENDS.build("nope")
Traceback (most recent call last):
    ...
repro.api.registry.UnknownEntryError: unknown backend 'nope'...
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.registry import BACKENDS
from repro.api.mixers import ring_read, ring_write

__all__ = ["BACKENDS", "ReferenceBackend", "PallasBackend",
           "pallas_supported"]


@dataclasses.dataclass(frozen=True)
class ReferenceBackend:
    """The plain-XLA engines — the oracle the pallas backend is held to."""

    name: str = "reference"

    def make_chunk_program(self, spec, engine: str):
        from repro.api import runner
        return runner.reference_chunk_program(spec, engine)

    def make_local_round_fn(self, spec, engine: str, part, delay: int,
                            schedule=None, graph=None) -> Callable:
        from repro.api import shard_node
        return shard_node.reference_local_round_fn(
            spec, engine, part, delay, schedule=schedule, graph=graph)


# ---------------------------------------------------------------------------
# pallas
# ---------------------------------------------------------------------------

def _round_kernels():
    from repro.kernels import round_fused
    return round_fused


def _interpret(flag: bool | None) -> bool:
    if flag is not None:
        return bool(flag)
    from repro.kernels.ops import _default_interpret
    return _default_interpret()


def _check_supported(spec) -> None:
    """The stages the fused round body hard-codes; everything else raises
    with the escape hatch named (backend='reference')."""
    from repro.api.rules import OMDLassoRule
    from repro.api.clippers import NoClipper, PerNodeL2Clipper

    rule = spec.resolve_local_rule()
    if not isinstance(rule, OMDLassoRule) or rule.prox_kind not in ("l1",
                                                                    "none"):
        raise ValueError(
            f"backend='pallas' fuses the paper's OMD + L1/identity prox "
            f"round body; got local_rule={type(rule).__name__}"
            f"{getattr(rule, 'prox_kind', '')!r} — use backend='reference'")
    clipper = spec.resolve_clipper()
    if not isinstance(clipper, (PerNodeL2Clipper, NoClipper)):
        raise ValueError(
            f"backend='pallas' folds clipping into a rank-1 coefficient, "
            f"which needs the per-node L2 clipper (or none); got "
            f"{type(clipper).__name__} — use backend='reference'")
    if spec.loss_and_grad is not None:
        raise ValueError(
            "backend='pallas' fuses the hinge loss/subgradient; a custom "
            "loss_and_grad needs backend='reference'")


def pallas_supported(spec) -> bool:
    """True when `backend="pallas"` accepts this spec's stage pipeline."""
    try:
        _check_supported(spec)
        return True
    except ValueError:
        return False


def _dense_mix_form(spec, mixer):
    """(A, diag, delay) dense mixing form for the fused mode, or None when
    the mixer has no fixed sparse lowering (time-varying, faulty, ...)."""
    if getattr(mixer, "schedule", None) is not None:
        return None                       # repro.faults: per-round weights
    from repro.api.shard_node import sparse_graph_and_delay
    try:
        graph, delay = sparse_graph_and_delay(mixer)
    except ValueError:
        return None
    A = jnp.asarray(graph.to_dense(), jnp.float32)
    diag = jnp.asarray(graph.diag(), jnp.float32)
    return A, diag, delay


def _pad2(a, m_pad: int, n_pad: int):
    m, n = a.shape
    return jnp.pad(a, ((0, m_pad - m), (0, n_pad - n)))


def _pad1(a, m_pad: int):
    return jnp.pad(a, (0, m_pad - a.shape[0]))


@dataclasses.dataclass(frozen=True)
class PallasBackend:
    """Fused-kernel execution of the round body (see module docstring).

    mode:            "auto" | "fused" | "hybrid" (auto fuses when possible).
    block_cols:      widest n-block per kernel grid step, in lanes; each
                     kernel narrows it to fit `round_fused.VMEM_LIMIT_BYTES`.
    interpret:       None -> interpret on the CPU platform only; a bool
                     pins it.
    """

    mode: str = "auto"
    block_cols: int = 512
    interpret: bool | None = None
    name: str = "pallas"

    def __post_init__(self):
        if self.mode not in ("auto", "fused", "hybrid"):
            raise ValueError(f"unknown pallas mode {self.mode!r}; expected "
                             "'auto', 'fused' or 'hybrid'")

    # -- mode selection ------------------------------------------------------

    def _dense_plan(self, spec, mixer):
        """The dense mixing form (A, diag, delay) when the round fuses, None
        when it runs hybrid. Raises ValueError when a kernel the chosen mode
        needs fits no block width under the VMEM budget — before anything
        is lowered."""
        rf = _round_kernels()
        m_pad, n_pad = rf._pad_rows(spec.nodes), rf._pad_cols(spec.dim)
        rf.col_block("round_stats", m_pad, n_pad, self.block_cols)
        if self.mode != "hybrid":
            dense = _dense_mix_form(spec, mixer)
            if dense is None and self.mode == "fused":
                raise ValueError(
                    f"backend='pallas' mode='fused' needs a fixed topology "
                    f"(got mixer={type(mixer).__name__}); use mode='hybrid' "
                    f"or 'auto'")
            if dense is not None:
                try:
                    rf.col_block("round_update", m_pad, n_pad,
                                 self.block_cols)
                    return dense
                except ValueError as err:
                    if self.mode == "fused":
                        raise ValueError(
                            f"backend='pallas' mode='fused': {err}; use "
                            f"mode='hybrid' or 'auto'") from None
        rf.col_block("dual_step", m_pad, n_pad, self.block_cols)
        return None

    # -- unsharded chunk program --------------------------------------------

    def make_chunk_program(self, spec, engine: str):
        if engine not in ("sim", "dist"):
            raise ValueError(
                f"unknown engine {engine!r}; expected 'sim' or 'dist'")
        round_fn = self._make_round_fn(spec, engine)

        def chunk_fn(state, xs, ys):
            return jax.lax.scan(round_fn, state, (xs, ys))

        from repro.api import runner
        init_fn = runner.reference_chunk_program(spec, engine)[1]
        return chunk_fn, init_fn

    def _make_round_fn(self, spec, engine: str) -> Callable:
        from repro.core.algorithm1 import SimState
        from repro.core.gossip import GossipState

        _check_supported(spec)
        rf = _round_kernels()
        m, n = spec.nodes, spec.dim
        if n is None:
            raise ValueError("RunSpec.dim is required by backend='pallas'")
        m_pad, n_pad = rf._pad_rows(m), rf._pad_cols(n)
        interpret = _interpret(self.interpret)
        mech = spec.resolve_mechanism()
        rule = spec.resolve_local_rule()
        clip = spec.resolve_clipper()
        omd = spec.omd_config()
        mixer = spec.resolve_mixer()
        schedule = getattr(mixer, "schedule", None)
        prox_l1 = rule.prox_kind == "l1"
        from repro.api.clippers import PerNodeL2Clipper
        clip_norm = clip.max_norm if isinstance(clip, PerNodeL2Clipper) \
            else None

        dense = self._dense_plan(spec, mixer)
        if dense is not None:
            A, diag_v, delay = dense
            A_pad = _pad2(A, m_pad, m_pad)
            diag_pad = _pad1(diag_v, m_pad)
        else:
            delay = int(getattr(mixer, "delay", 0))

        def stats_and_coeff(theta_p, x_p, y, ctx):
            dot, xsq, nnz, wbdot, _ = rf.round_stats(
                theta_p, x_p, ctx.lam_t, m, prox_l1=prox_l1,
                block_cols=self.block_cols, interpret=interpret)
            dot, xsq, nnz, wbdot = dot[:m], xsq[:m], nnz[:m], wbdot[:m]
            margin = y * dot
            loss = jnp.maximum(1.0 - margin, 0.0)
            correct = (jnp.sign(dot) == y).astype(jnp.float32)
            active = (margin < 1.0).astype(jnp.float32)
            if clip_norm is None:
                factor = 1.0
            else:
                gnorm = active * jnp.sqrt(xsq)
                factor = jnp.minimum(1.0, clip_norm
                                     / jnp.maximum(gnorm, 1e-12))
            coeff = -(active * y) * factor
            wb_loss = rf.node_sum(jnp.maximum(1.0 - y * wbdot, 0.0),
                                  interpret=interpret) / m
            # zero COUNT first (small ints are exact in f32), then divide —
            # bit-equal to the reference's mean-of-indicators
            sparsity = (m * n - jnp.sum(nnz)) / (m * n)
            return coeff, loss, correct, wb_loss, sparsity

        def round_fn(state, batch):
            from repro.core.algorithm1 import RoundOutput

            x, y = batch
            sim = engine == "sim"
            theta = state.theta if sim else state.theta["w"]
            hist = state.history
            if not sim and hist is not None:
                hist = hist["w"]
            ctx = omd.step_context(state.t + 1)
            theta_p = _pad2(theta, m_pad, n_pad)
            x_p = _pad2(x, m_pad, n_pad)
            coeff, loss, correct, wb_loss, sparsity = stats_and_coeff(
                theta_p, x_p, y, ctx)

            # the engines' exact noise draw — bit-identical PRNG stream
            key, sub = jax.random.split(state.key)
            scale = mech.scale(ctx.alpha_t, n)
            delta = mech.sample(sub, (m, n), scale)

            alive = (schedule.alive_f32(state.t)
                     if schedule is not None and schedule.has_crashes
                     else jnp.ones((m,), jnp.float32))

            if dense is not None:
                if delay:
                    tilde = theta + delta
                    hist = ring_write(hist, state.t, tilde)
                    recv = ring_read(hist, state.t, delay, tilde)
                    recv_p, use_recv = _pad2(recv, m_pad, n_pad), 1.0
                else:
                    recv_p, use_recv = theta_p, 0.0
                theta_next_p, _ = rf.round_update(
                    A_pad, theta_p, _pad2(delta, m_pad, n_pad), x_p, recv_p,
                    _pad1(coeff, m_pad), diag_pad, _pad1(alive, m_pad),
                    ctx.alpha_t, use_recv, mech.noise_self,
                    block_cols=self.block_cols, interpret=interpret)
            else:
                tilde = theta + delta
                if delay:
                    hist = ring_write(hist, state.t, tilde)
                    mixed = mixer.mix_history(theta, tilde, hist,
                                              mech.noise_self, state.t)
                else:
                    mixed = mixer.mix(theta, tilde, mech.noise_self, state.t)
                theta_next_p = rf.dual_step(
                    _pad2(mixed, m_pad, n_pad), x_p, theta_p,
                    _pad1(coeff, m_pad), _pad1(alive, m_pad), ctx.alpha_t,
                    block_cols=self.block_cols, interpret=interpret)
            theta_next = theta_next_p[:m, :n]

            out = RoundOutput(loss=loss, w_bar_loss=wb_loss,
                              sparsity=sparsity, correct=correct)
            if sim:
                new_state = SimState(theta=theta_next, t=state.t + 1,
                                     key=key, history=hist)
            else:
                new_state = GossipState(
                    theta={"w": theta_next}, t=state.t + 1, key=key,
                    history=None if hist is None else {"w": hist})
            return new_state, out

        return round_fn

    # -- node-sharded local round (hybrid: halo exchange stays outside) ----

    def make_local_round_fn(self, spec, engine: str, part, delay: int,
                            schedule=None, graph=None) -> Callable:
        from repro.core.algorithm1 import RoundOutput, SimState
        from repro.core.gossip import GossipState
        from repro.api.shard_node import ShardedSparseMixer
        from repro.api.clippers import PerNodeL2Clipper
        from repro.core.privacy import slice_rows

        _check_supported(spec)
        rf = _round_kernels()
        m, n = part.m, spec.dim
        block = part.block
        blk_pad, n_pad = rf._pad_rows(block), rf._pad_cols(n)
        for kernel in ("round_stats", "dual_step"):
            rf.col_block(kernel, blk_pad, n_pad, self.block_cols)
        interpret = _interpret(self.interpret)
        mech = spec.resolve_mechanism()
        rule = spec.resolve_local_rule()
        clip = spec.resolve_clipper()
        omd = spec.omd_config()
        prox_l1 = rule.prox_kind == "l1"
        clip_norm = clip.max_norm if isinstance(clip, PerNodeL2Clipper) \
            else None
        if schedule is not None:
            from repro.faults.mixers import FaultyShardedSparseMixer
            smixer = FaultyShardedSparseMixer(part, graph, schedule,
                                              delay=delay)
        else:
            smixer = ShardedSparseMixer(part, delay=delay)

        def round_fn(state, batch):
            x, y = batch                          # (block, n), (block,)
            d = jax.lax.axis_index("node")
            gidx = d * block + jnp.arange(block)
            mask = (gidx < m).astype(jnp.float32)
            theta = state.theta if engine == "sim" else state.theta["w"]
            hist = state.history
            if engine == "dist" and hist is not None:
                hist = hist["w"]
            ctx = omd.step_context(state.t + 1)

            theta_p = _pad2(theta, blk_pad, n_pad)
            x_p = _pad2(x, blk_pad, n_pad)
            dot, xsq, nnz, _, wsum = rf.round_stats(
                theta_p, x_p, ctx.lam_t, m, prox_l1=prox_l1,
                block_cols=self.block_cols, interpret=interpret)
            dot, xsq, nnz = dot[:block], xsq[:block], nnz[:block]
            margin = y * dot
            loss = jnp.maximum(1.0 - margin, 0.0)
            correct = (jnp.sign(dot) == y).astype(jnp.float32)
            active = (margin < 1.0).astype(jnp.float32)
            if clip_norm is None:
                factor = 1.0
            else:
                gnorm = active * jnp.sqrt(xsq)
                factor = jnp.minimum(1.0, clip_norm
                                     / jnp.maximum(gnorm, 1e-12))
            coeff = -(active * y) * factor

            # global w_bar: the kernel's per-shard column sums, psum'd —
            # then one XLA matvec for the w_bar hinge terms
            w_bar = jax.lax.psum(wsum[:n], "node") / m
            wb_terms = jnp.maximum(
                1.0 - y * jnp.sum(w_bar[None, :] * x, axis=-1), 0.0)
            wb_loss = jax.lax.psum(jnp.sum(wb_terms * mask), "node") / m
            zeros = jnp.sum((n - nnz) * mask)
            sparsity = jax.lax.psum(zeros, "node") / (m * n)

            key, sub = jax.random.split(state.key)
            scale = mech.scale(ctx.alpha_t, n)
            delta = mech.sample_rows(sub, (m, n), d * block, block, scale)
            tilde = theta + delta

            # mixing stays in XLA: the ppermute halo exchange + segment_sum
            # of ShardedSparseMixer, exactly as the reference sharded round
            if delay:
                hist = ring_write(hist, state.t, tilde)
                mixed = smixer.mix_history(theta, tilde, hist,
                                           mech.noise_self, state.t)
            else:
                mixed = smixer.mix(theta, tilde, mech.noise_self, state.t)

            alive_blk = jnp.ones((block,), jnp.float32)
            if schedule is not None and schedule.has_crashes:
                alive_blk = slice_rows(schedule.alive_f32(state.t),
                                       d * block, block)
            theta_next = rf.dual_step(
                _pad2(mixed, blk_pad, n_pad), x_p, theta_p,
                _pad1(coeff, blk_pad), _pad1(alive_blk, blk_pad),
                ctx.alpha_t, block_cols=self.block_cols,
                interpret=interpret)[:block, :n]

            out = RoundOutput(loss=loss, w_bar_loss=wb_loss,
                              sparsity=sparsity, correct=correct)
            if engine == "sim":
                new_state = SimState(theta=theta_next, t=state.t + 1,
                                     key=key, history=hist)
            else:
                new_state = GossipState(
                    theta={"w": theta_next}, t=state.t + 1, key=key,
                    history=None if hist is None else {"w": hist})
            return new_state, out

        return round_fn


@BACKENDS.register("reference")
def _reference() -> ReferenceBackend:
    """Plain-XLA engines (the correctness oracle)."""
    return ReferenceBackend()


@BACKENDS.register("pallas")
def _pallas(mode: str = "auto", block_cols: int = 512,
            interpret: bool | None = None) -> PallasBackend:
    """Fused Pallas round body (see docs/kernels.md)."""
    return PallasBackend(mode=mode, block_cols=block_cols,
                         interpret=interpret)
