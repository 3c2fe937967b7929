"""Node-axis sharding — run the gossip round with theta split across devices.

Everything before this module scales the *seed* axis; the node axis — the
paper's actual "m data centers" dimension — lived on one device, bounded by
the dense n x n mixing matrix. This module shards it:

* the topology comes in as a `repro.core.graph.SparseGraph` (edge list,
  O(edges) memory) via `SparseMixer` — `sparse_graph_and_delay` also
  converts the fixed dense mixers (ring / single-matrix dense stacks) so
  existing specs work unchanged;
* `partition_graph` splits the m rows into D contiguous blocks of
  ``block = ceil(m / D)`` rows (rows m..m_pad-1 are padding: no edges, zero
  mask) and groups the edges of each destination block by **shard offset**
  ``(src_shard - dst_shard) % D``;
* `ShardedSparseMixer` runs one gossip exchange per used offset: a
  `lax.ppermute` rotates the neighbor block of theta~ across the ("node",)
  mesh axis (the halo exchange — offset 0 is device-local and free), then a
  weighted `segment_sum` scatters it into the local rows;
* `make_node_chunk_fn` wraps the whole per-chunk `lax.scan` in `shard_map`
  so `repro.api.run(..., node_devices=D)` and
  `run_batch(..., node_devices=D)` (the ("seed","node") grid) drive it like
  any other chunk program. State crossing the wrapper stays GLOBAL and
  unpadded, so checkpoints restore under any device count.

Equivalence contract (tests/test_shard_node.py): the per-round Laplace
noise is bit-identical to the dense engines — each shard draws only its
own rows of the same (m, n) stream from the same per-round key
(`Mechanism.sample_rows`), bit for bit — so a sharded run differs from
dense `run()` only by float32 reduction order (segment_sum vs tensordot,
psum'd metrics); the suite asserts the bound.

>>> import jax
>>> from repro.api import RunSpec
>>> from repro.api.shard_node import make_node_chunk_fn
>>> from repro.launch.mesh import make_mesh
>>> spec = RunSpec(nodes=6, dim=4, horizon=4, eps=1.0, alpha0=0.5,
...                lam=0.01, stream="drift", mixer="sparse",
...                mixer_options={"topology": "ring"})
>>> mesh = make_mesh((1,), ("node",))        # 1 device: same program, D=1
>>> chunk_fn, init_fn = make_node_chunk_fn(spec, "sim", mesh)
>>> state = init_fn(jax.random.PRNGKey(spec.seed))
>>> xs, ys = spec.resolve_stream().chunk(0, 4)
>>> state, outs = jax.jit(chunk_fn)(state, xs, ys)
>>> outs.loss.shape, state.theta.shape
((4, 6), (6, 4))
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.mixers import (DelayedMixer, DenseMatrixMixer, MixerBase,
                              RingRollMixer, SparseMixer, ring_write)
from repro.api.spec import RunSpec

__all__ = ["sparse_graph_and_delay", "NodePartition", "partition_graph",
           "ShardedSparseMixer", "make_node_chunk_fn", "resolve_node_mesh",
           "reference_local_round_fn"]


def sparse_graph_and_delay(mixer) -> tuple[Any, int]:
    """(SparseGraph, delay) behind a resolved mixer, for sharding.

    Accepts `SparseMixer` (native), `RingRollMixer` (exact `ring_edges`
    form) and fixed single-matrix `DenseMatrixMixer` stacks (converted via
    `SparseGraph.from_dense`), optionally wrapped in `DelayedMixer`.
    Time-varying schedules, per-edge heterogeneous delays and the
    no-communication mixer have no fixed sparse form and raise.
    """
    from repro.core.graph import SparseGraph, ring_edges

    delay = int(getattr(mixer, "delay", 0))
    inner = mixer.inner if isinstance(mixer, DelayedMixer) else mixer
    if isinstance(inner, SparseMixer):
        return inner.graph, delay
    if isinstance(inner, RingRollMixer):
        return ring_edges(inner.m, self_weight=inner.self_weight), delay
    if isinstance(inner, DenseMatrixMixer):
        stack = np.asarray(inner.stack)
        if stack.shape[0] != 1:
            raise ValueError(
                f"mixer {inner.name!r} is a time-varying dense schedule "
                f"({stack.shape[0]} matrices); node sharding needs one fixed "
                "topology — use mixer='sparse' or a single-matrix stack")
        return SparseGraph.from_dense(stack[0], name=inner.name), delay
    raise ValueError(
        f"{type(inner).__name__} cannot be node-sharded: no fixed sparse "
        "form (use mixer='sparse' with a ring/torus/... topology)")


@dataclasses.dataclass(frozen=True)
class NodePartition:
    """Edges of a SparseGraph regrouped for a D-way contiguous row split.

    ``offsets`` holds one entry per used shard offset o = (src_shard -
    dst_shard) % D: (o, dst_local (D, E_o), src_local (D, E_o), weight
    (D, E_o)) — row d of each array is destination shard d's edges whose
    sources live on shard (d + o) % D, zero-padded to the widest shard
    (weight 0 edges scatter nothing). ``diag_blocks`` is the (D, block)
    self-weight table; padding rows m..m_pad-1 carry no edges and weight 0.
    """

    m: int
    devices: int
    block: int           # rows per device = ceil(m / devices)
    m_pad: int           # block * devices
    offsets: tuple       # ((o, dst_local, src_local, weight), ...)
    diag_blocks: Any     # (D, block) float32


def partition_graph(graph, devices: int) -> NodePartition:
    """Split a SparseGraph's edges by destination shard and source offset."""
    D = int(devices)
    if D < 1:
        raise ValueError(f"partition_graph needs devices >= 1, got {D}")
    m = int(graph.m)
    block = -(-m // D)
    m_pad = block * D
    dst = np.asarray(graph.dst, np.int64)
    src = np.asarray(graph.src, np.int64)
    weight = np.asarray(graph.weight, np.float32)
    dst_shard = dst // block
    offs = (src // block - dst_shard) % D
    offsets = []
    for o in sorted(set(int(v) for v in offs)):
        per_dev = [np.flatnonzero((offs == o) & (dst_shard == d))
                   for d in range(D)]
        width = max(len(ix) for ix in per_dev)
        dl = np.zeros((D, width), np.int32)
        sl = np.zeros((D, width), np.int32)
        ww = np.zeros((D, width), np.float32)
        for d, ix in enumerate(per_dev):
            k = len(ix)
            dl[d, :k] = dst[ix] - d * block
            sl[d, :k] = src[ix] % block
            ww[d, :k] = weight[ix]
        offsets.append((o, dl, sl, ww))
    diag = np.zeros((m_pad,), np.float32)
    diag[:m] = np.asarray(graph.diag(), np.float32)
    return NodePartition(m=m, devices=D, block=block, m_pad=m_pad,
                         offsets=tuple(offsets),
                         diag_blocks=diag.reshape(D, block))


class ShardedSparseMixer(MixerBase):
    """SparseMixer split over a mesh axis: ppermute halo + local segment_sum.

    Must run inside `shard_map` with ``axis`` in the mesh. Each used source
    offset costs one `lax.ppermute` of the whole local theta~ block (offset
    0 — the bulk of a well-laid-out graph — stays device-local); the mixing
    algebra (mix / mix_delayed / mix_history) is inherited from MixerBase so
    noise placement and delay handling match the unsharded mixers exactly.
    """

    def __init__(self, part: NodePartition, delay: int = 0,
                 axis: str = "node"):
        self.part = part
        self.m = part.m
        self.delay = int(delay)
        self.axis = axis
        self._offsets = tuple(
            (o, jnp.asarray(dl), jnp.asarray(sl), jnp.asarray(ww))
            for o, dl, sl, ww in part.offsets)
        self._diag_blocks = jnp.asarray(part.diag_blocks)

    def apply(self, x, t):
        D = self.part.devices
        d = jax.lax.axis_index(self.axis)
        out = jnp.zeros(x.shape, jnp.float32)
        for o, dl, sl, ww in self._offsets:
            halo = x if o == 0 else jax.lax.ppermute(
                x, self.axis, perm=[(j, (j - o) % D) for j in range(D)])
            w = ww[d].reshape((-1,) + (1,) * (x.ndim - 1))
            vals = w * halo[sl[d]].astype(jnp.float32)
            out = out + jax.ops.segment_sum(vals, dl[d],
                                            num_segments=self.part.block)
        return out.astype(x.dtype)

    def diag(self, t):
        return self._diag_blocks[jax.lax.axis_index(self.axis)]


# -- the node-sharded chunk program ------------------------------------------

def _pad_axis(x, pad: int, axis: int):
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _map_node_leaves(state, fn):
    """Apply fn to every theta/history leaf (node axis is always ndim-2)."""
    theta = jax.tree_util.tree_map(fn, state.theta)
    hist = state.history
    if hist is not None:
        hist = jax.tree_util.tree_map(fn, hist)
    return state._replace(theta=theta, history=hist)


def _pad_state(state, pad: int):
    return _map_node_leaves(state, lambda l: _pad_axis(l, pad, l.ndim - 2))


def _unpad_state(state, m: int):
    return _map_node_leaves(state, lambda l: l[..., :m, :])


def _state_pspecs(template, lead: tuple):
    from jax.sharding import PartitionSpec as P
    theta = jax.tree_util.tree_map(lambda _: P(*lead, "node"), template.theta)
    hist = template.history
    if hist is not None:
        hist = jax.tree_util.tree_map(lambda _: P(*lead, None, "node"), hist)
    return template._replace(theta=theta, t=P(*lead), key=P(*lead),
                             history=hist)


def reference_local_round_fn(spec: RunSpec, engine: str, part: NodePartition,
                             delay: int, schedule=None,
                             graph=None) -> Callable:
    """One gossip round over THIS shard's block of nodes (reference backend;
    `make_node_chunk_fn` dispatches here — or to the backend's fused
    variant — via ``spec.resolve_backend()``).

    Mirrors `Algorithm1.round` / `GossipDP.update` term for term; the only
    cross-shard traffic is the mixer's halo exchange and three metric psums.
    The Laplace draw replays the dense engines' stream bit for bit: each
    shard draws only its block of rows of the (m, n) sample from the same
    per-round key (`Mechanism.sample_rows`); the padding rows of the last
    shard come out zero.

    ``schedule`` (a `repro.faults.FaultSchedule`, with the global ``graph``
    it was wrapped around) swaps the mixer for `FaultyShardedSparseMixer`
    and freezes crashed rows of the local block, mirroring the unsharded
    engines' fault hooks.
    """
    from repro.core import prox
    from repro.core.algorithm1 import (RoundOutput, SimState,
                                       hinge_loss_and_grad)
    from repro.core.gossip import GossipState
    from repro.core.privacy import slice_rows

    m, n = part.m, spec.dim
    block = part.block
    mech = spec.resolve_mechanism()
    rule = spec.resolve_local_rule()
    clipper = spec.resolve_clipper()
    omd = spec.omd_config()
    loss_and_grad = spec.loss_and_grad or hinge_loss_and_grad
    if schedule is not None:
        from repro.faults.mixers import FaultyShardedSparseMixer
        smixer = FaultyShardedSparseMixer(part, graph, schedule, delay=delay)
    else:
        smixer = ShardedSparseMixer(part, delay=delay)

    def round_fn(state, batch):
        x, y = batch                              # (block, n), (block,)
        d = jax.lax.axis_index("node")
        gidx = d * block + jnp.arange(block)
        mask = (gidx < m).astype(jnp.float32)     # 0 on the padding rows
        theta = state.theta if engine == "sim" else state.theta["w"]
        hist = state.history
        if engine == "dist" and hist is not None:
            hist = hist["w"]
        ctx = omd.step_context(state.t + 1)

        w = rule.primal(theta, ctx)
        loss, grad = loss_and_grad(w, x, y)
        correct = (jnp.sign(jnp.einsum("mn,mn->m", w, x)) == y
                   ).astype(jnp.float32)
        grad, _ = clipper.clip(grad)

        key, sub = jax.random.split(state.key)
        scale = mech.scale(ctx.alpha_t, n)
        delta = mech.sample_rows(sub, (m, n), d * block, block, scale)
        tilde = theta + delta

        if delay:
            hist = ring_write(hist, state.t, tilde)
            mixed = smixer.mix_history(theta, tilde, hist, mech.noise_self,
                                       state.t)
        else:
            mixed = smixer.mix(theta, tilde, mech.noise_self, state.t)
        theta_next = rule.dual_step(mixed, grad, ctx)
        if schedule is not None and schedule.has_crashes:
            # crashed rows of this block freeze (repro.faults), matching the
            # unsharded engines' hook; pad rows stay zero either way
            alive_blk = slice_rows(schedule.alive_mask(state.t), d * block,
                                   block)
            theta_next = jnp.where(alive_blk[:, None], theta_next, theta)

        # global metrics: masked partial sums psum'd over the mesh axis —
        # same algebra as the dense engines up to reduction order
        w_bar = jax.lax.psum(jnp.sum(w * mask[:, None], axis=0), "node") / m
        wb_terms = jnp.maximum(1.0 - y * jnp.sum(w_bar[None, :] * x, axis=-1),
                               0.0)
        wb_loss = jax.lax.psum(jnp.sum(wb_terms * mask), "node") / m
        zeros = jnp.sum((jnp.abs(w) <= 0.0).astype(jnp.float32)
                        * mask[:, None])
        sparsity = jax.lax.psum(zeros, "node") / (m * n)

        out = RoundOutput(loss=loss, w_bar_loss=wb_loss, sparsity=sparsity,
                          correct=correct)
        if engine == "sim":
            new_state = SimState(theta=theta_next, t=state.t + 1, key=key,
                                 history=hist)
        else:
            new_state = GossipState(theta={"w": theta_next}, t=state.t + 1,
                                    key=key,
                                    history=None if hist is None
                                    else {"w": hist})
        return new_state, out

    return round_fn


def resolve_node_mesh(node_devices, mesh):
    """The mesh carrying the "node" axis, or None for the unsharded path.

    Mirrors `runner._resolve_seed_mesh`: a prebuilt ``mesh`` must carry a
    "node" axis; ``node_devices`` goes through `launch.mesh.node_mesh`
    (None / 0 / 1 -> None, "auto" -> every local device).
    """
    if mesh is not None:
        if "node" not in mesh.axis_names:
            raise ValueError(
                f"node sharding needs a mesh with a 'node' axis, got axes "
                f"{tuple(mesh.axis_names)}")
        return mesh
    if node_devices is None:
        return None
    from repro.launch.mesh import node_mesh
    return node_mesh(node_devices)


def make_node_chunk_fn(spec: RunSpec, engine: str, mesh,
                       batched: bool = False,
                       obs=None) -> tuple[Callable, Callable]:
    """Node-sharded (chunk_fn, init_fn) — drop-in for `make_chunk_program`.

    chunk_fn consumes and returns GLOBAL, unpadded state / data: the node
    padding (m -> m_pad = ceil(m/D)*D) and the `shard_map` over ``mesh``
    live inside, so `run`'s checkpoint / resume / metrics logic — and
    device-count portability of checkpoints — need no changes. With
    ``batched=True`` the per-chunk scan is vmapped over a leading seed axis
    and every spec gains a leading "seed" dim (the ("seed","node") grid
    `run_batch` uses).

    Sets the gauge ``shard.noise_rows_drawn`` of ``obs`` (default: the
    ambient `repro.obs.active()`, when enabled): the rows of the (m, n)
    noise sample each shard draws per round — ``block`` when the
    mechanism draws its rows alone, m when it draws the whole sample.
    """
    from jax.sharding import PartitionSpec as P
    from repro import obs as obslib
    from repro.core.algorithm1 import RoundOutput

    if engine not in ("sim", "dist"):
        raise ValueError(f"unknown engine {engine!r}; expected 'sim' or 'dist'")
    if "node" in getattr(mesh, "axis_names", ()):
        D = int(mesh.shape["node"])
    else:
        raise ValueError(
            f"make_node_chunk_fn needs a mesh with a 'node' axis, got "
            f"{tuple(getattr(mesh, 'axis_names', ()))}")
    lead = ("seed",) if batched else ()
    if batched and "seed" not in mesh.axis_names:
        raise ValueError("batched node sharding needs a ('seed','node') mesh")

    mixer = spec.resolve_mixer()
    schedule = getattr(mixer, "schedule", None)
    if schedule is not None:
        # repro.faults: the spec resolved to a faulty mixer — shard its
        # INNER edge list and rebuild the fault masks per device block
        from repro.faults.mixers import FaultySparseMixer
        if not isinstance(mixer, FaultySparseMixer):
            raise ValueError(
                f"node sharding under faults needs the sparse edge-list path "
                f"(mixer='sparse' or a ring), got {type(mixer).__name__}")
        if schedule.max_extra:
            raise ValueError(
                "stragglers are not supported under node sharding — "
                "per-class delay rings do not shard; drop "
                "straggler_rate/stragglers or run unsharded")
        graph, delay = mixer.inner.graph, mixer.base_delay
    else:
        graph, delay = sparse_graph_and_delay(mixer)
    if int(graph.m) != int(spec.nodes):
        raise ValueError(f"graph has m={graph.m} nodes but RunSpec.nodes="
                         f"{spec.nodes}")
    part = partition_graph(graph, D)
    m, pad = part.m, part.m_pad - part.m
    # the spec's backend builds the per-shard round body ("reference" is
    # reference_local_round_fn above; "pallas" swaps in the fused stats +
    # dual-step kernels — the ppermute halo exchange stays out here either
    # way, in the sharded mixer the round body calls)
    round_fn = spec.resolve_backend().make_local_round_fn(
        spec, engine, part, delay, schedule=schedule, graph=graph)

    def local_chunk(state, xs, ys):
        return jax.lax.scan(round_fn, state, (xs, ys))

    body = jax.vmap(local_chunk) if batched else local_chunk

    # init states are built by the UNSHARDED reference program: global,
    # unpadded — the same pytree a dense run initializes, so checkpoints
    # interchange across backends and device counts
    from repro.api.runner import reference_chunk_program
    init_fn = reference_chunk_program(spec, engine)[1]

    template = init_fn(jax.random.PRNGKey(0))
    tel = obs if obs is not None else obslib.active()
    if tel.enabled:
        tel.metrics.gauge("shard.noise_rows_drawn").set(
            spec.resolve_mechanism().rows_drawn(
                template.key, (m, spec.dim), part.block))
    state_spec = _state_pspecs(template, lead)
    data_spec = P(*lead, None, "node")
    outs_spec = RoundOutput(loss=data_spec, w_bar_loss=P(*lead),
                            sparsity=P(*lead), correct=data_spec)
    smapped = jax.shard_map(body, mesh=mesh,
                            in_specs=(state_spec, data_spec, data_spec),
                            out_specs=(state_spec, outs_spec),
                            check_vma=False)

    def chunk_fn(state, xs, ys):
        state = _pad_state(state, pad)
        xs = _pad_axis(xs, pad, xs.ndim - 2)
        ys = _pad_axis(ys, pad, ys.ndim - 1)
        state, outs = smapped(state, xs, ys)
        outs = outs._replace(loss=outs.loss[..., :m],
                             correct=outs.correct[..., :m])
        return _unpad_state(state, m), outs

    return chunk_fn, init_fn
