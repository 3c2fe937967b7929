"""`repro.api.run` — one call from RunSpec to RunResult, for either engine.

Before this module existed every benchmark and example hand-rolled its own
driving loop (and none of them did privacy accounting). `run` closes the
loop: it resolves the spec's Stream (STREAMS registry), drives the whole
horizon under a jitted `lax.scan` per chunk on EITHER engine — the dense
simulator (`engine="sim"`) or the node-stacked distributed strategy
(`engine="dist"`) — threads a `PrivacyAccountant` into a per-round eps
ledger, records the regret/accuracy trajectories, and supports periodic
checkpointing with bit-identical resume through `repro.checkpoint`.

Both engines consume the same per-absolute-round stream chunks and the same
PRNG key, so a seeded run produces bit-identical iterates under either
engine (including the Laplace noise — see the single-leaf key note in
`core.gossip.gossip_mix_tree`).

Execution knobs travel as one frozen `ExecConfig` (`repro.api.exec_config`)
passed via ``exec=``; the legacy keyword arguments still work through a
deprecation shim that forwards into ExecConfig and warns once.

>>> from repro.api import ExecConfig, RunSpec, run
>>> spec = RunSpec(nodes=2, dim=8, horizon=6, eps=1.0, alpha0=0.5,
...                lam=0.01, stream="drift", stream_options={"period": 2})
>>> cfg = ExecConfig(chunk_rounds=3, compute_regret=False, warmup=False)
>>> res = run(spec, engine="sim", exec=cfg)
>>> res.rounds, res.correct.shape, float(res.eps_ledger[-1])
(6, (6, 2), 1.0)
>>> dist = run(spec, engine="dist", exec=cfg)
>>> bool((res.final_w == dist.final_w).all())     # seeded, bit-identical
True

How the round body executes is the spec's business, not the runner's: the
chunk builders dispatch through ``spec.resolve_backend()`` (BACKENDS
registry — "reference" XLA engines or the fused "pallas" kernels, see
`repro.api.backends`), so every path here — run, run_batch, the
node-sharded mesh — honours ``RunSpec.backend`` without special cases.

`run` also drives arbitrary step functions (`step_fn=`) so the train CLI's
LM loops share this exact loop — metrics, logging, accounting, checkpoints
— instead of reimplementing it.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obslib
from repro.api.exec_config import ExecConfig, resolve_exec
from repro.api.spec import RunSpec
from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.core.privacy import PrivacyAccountant
from repro.metrics import CSVLogger, MetricTracker

__all__ = ["run", "run_batch", "RunResult", "make_chunk_fn",
           "make_chunk_program", "reference_chunk_program"]


# -- JSON round-trip ---------------------------------------------------------
#
# The sweep store (repro.sweep.store) persists one RunResult per record and
# must reconstruct it EXACTLY: trajectories, eps ledger, final parameters and
# (optionally) the raw engine state. float32 values survive the trip through
# Python floats untouched (float32 ⊂ float64 and repr round-trips), so the
# regression tests can assert bit equality, not closeness.

def _encode_tree(obj: Any) -> Any:
    """JSON-able encoding of a (possibly nested) engine state / array."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, (np.ndarray, jnp.ndarray, np.generic)):
        arr = np.asarray(jax.device_get(obj))
        return {"__ndarray__": arr.tolist(), "dtype": str(arr.dtype),
                "shape": list(arr.shape)}
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):   # NamedTuple
        return {"__namedtuple__": type(obj).__name__,
                "fields": {f: _encode_tree(getattr(obj, f))
                           for f in obj._fields}}
    if isinstance(obj, dict):
        return {"__dict__": {str(k): _encode_tree(v) for k, v in obj.items()}}
    if isinstance(obj, (list, tuple)):
        return {"__list__": [_encode_tree(v) for v in obj],
                "tuple": isinstance(obj, tuple)}
    raise TypeError(f"cannot encode {type(obj).__name__} for the JSON record")


def _state_types() -> dict:
    from repro.core.algorithm1 import SimState
    from repro.core.gossip import GossipState
    return {"SimState": SimState, "GossipState": GossipState}


def _decode_tree(obj: Any) -> Any:
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if "__ndarray__" in obj:
        return np.asarray(obj["__ndarray__"],
                          dtype=obj["dtype"]).reshape(obj["shape"])
    if "__namedtuple__" in obj:
        cls = _state_types()[obj["__namedtuple__"]]
        return cls(**{k: _decode_tree(v) for k, v in obj["fields"].items()})
    if "__dict__" in obj:
        return {k: _decode_tree(v) for k, v in obj["__dict__"].items()}
    if "__list__" in obj:
        seq = [_decode_tree(v) for v in obj["__list__"]]
        return tuple(seq) if obj.get("tuple") else seq
    raise TypeError(f"cannot decode record node {obj!r}")


@dataclasses.dataclass
class RunResult:
    """Everything a finished run knows about itself.

    Stream runs fill the trajectory arrays (per-round, horizon-length,
    covering [start_round, rounds)); custom step_fn runs fill ``history``
    (one metrics dict per step) instead. ``eps_ledger[t]`` is the cumulative
    privacy guarantee after round start_round + t + 1.
    """

    engine: str
    rounds: int
    wall_clock: float            # seconds, post-compile (see warmup=)
    rounds_per_sec: float
    stream: str | None = None
    start_round: int = 0         # > 0 when resumed from a checkpoint
    eps_ledger: np.ndarray | None = None
    privacy: dict = dataclasses.field(default_factory=dict)
    loss: np.ndarray | None = None        # (T, m) per-node hinge losses
    w_bar_loss: np.ndarray | None = None  # (T,) loss of the averaged w
    correct: np.ndarray | None = None     # (T, m) prediction correctness
    sparsity: np.ndarray | None = None    # (T,) zero-fraction of w
    regret: np.ndarray | None = None      # (T,) cumulative (Definition 3)
    connectivity: np.ndarray | None = None  # (T,) surviving off-diag mixing
    #                                         weight fraction (faulty runs)
    accuracy: float | None = None         # mean correctness, last 20%
    final_w: np.ndarray | None = None     # (m, n) final primal parameters
    final_state: Any = None               # engine state (checkpointable)
    history: list | None = None           # custom-mode per-step metrics
    metrics: dict = dataclasses.field(default_factory=dict)

    def accuracy_curve(self, window: int = 50) -> np.ndarray:
        """Moving-window mean accuracy over the horizon."""
        correct = self.correct.mean(axis=1)
        c = np.cumsum(np.insert(correct, 0, 0.0))
        return (c[window:] - c[:-window]) / window

    def summary(self) -> dict:
        return {
            "engine": self.engine,
            "stream": self.stream,
            "rounds": self.rounds,
            "wall_clock_s": round(self.wall_clock, 3),
            "rounds_per_sec": round(self.rounds_per_sec, 2),
            "accuracy": self.accuracy,
            "regret_final": (None if self.regret is None
                             else float(self.regret[-1])),
            "eps_total": self.privacy.get("eps_total"),
        }

    _ARRAY_FIELDS = ("eps_ledger", "loss", "w_bar_loss", "correct",
                     "sparsity", "regret", "connectivity", "final_w")

    def to_record(self, include_state: bool = False) -> dict:
        """JSON-able dict that `from_record` reconstructs exactly.

        Every trajectory array, the eps ledger and final_w round-trip
        bit-for-bit (float32 values survive the trip through JSON floats
        untouched). ``include_state=True`` additionally serializes the raw
        engine state (`SimState` / `GossipState` pytree) so a stored record
        can seed a resumed run; the sweep store leaves it off by default to
        keep the JSONL lean.
        """
        rec: dict[str, Any] = {
            "engine": self.engine,
            "rounds": self.rounds,
            "start_round": self.start_round,
            "wall_clock": self.wall_clock,
            "rounds_per_sec": self.rounds_per_sec,
            "stream": self.stream,
            "accuracy": self.accuracy,
            "privacy": dict(self.privacy),
            "metrics": dict(self.metrics),
            "history": self.history,
        }
        for f in self._ARRAY_FIELDS:
            v = getattr(self, f)
            rec[f] = None if v is None else _encode_tree(np.asarray(v))
        rec["final_state"] = (_encode_tree(jax.device_get(self.final_state))
                             if include_state and self.final_state is not None
                             else None)
        return rec

    @classmethod
    def from_record(cls, rec: dict) -> "RunResult":
        kw = {k: rec[k] for k in ("engine", "rounds", "start_round",
                                  "wall_clock", "rounds_per_sec", "stream",
                                  "accuracy")}
        kw["privacy"] = dict(rec.get("privacy") or {})
        kw["metrics"] = dict(rec.get("metrics") or {})
        kw["history"] = rec.get("history")
        for f in cls._ARRAY_FIELDS:
            v = rec.get(f)
            kw[f] = None if v is None else _decode_tree(v)
        fs = rec.get("final_state")
        kw["final_state"] = None if fs is None else _decode_tree(fs)
        return cls(**kw)


def make_chunk_program(spec: RunSpec, engine: str) -> tuple[Callable, Callable]:
    """(chunk_fn, init_fn) for one engine, via the spec's backend.

    chunk_fn(state, xs, ys) scans the round body over a chunk of rounds and
    returns (state, RoundOutput-stacked trajectories); init_fn(key) builds
    the engine state for one PRNG key. The program is seed-independent —
    only the key (and the stream data fed to chunk_fn) vary per seed, which
    is what lets `run_batch` build ONE program and S init states.

    Dispatches through ``spec.resolve_backend()`` (BACKENDS registry):
    backend="reference" is `reference_chunk_program` below; "pallas" swaps
    the round body for the fused kernels of `repro.kernels.round_fused`
    while keeping the same state pytrees, PRNG stream and scan structure.
    """
    return spec.resolve_backend().make_chunk_program(spec, engine)


def reference_chunk_program(spec: RunSpec,
                            engine: str) -> tuple[Callable, Callable]:
    """(chunk_fn, init_fn) of the plain-XLA engines — the reference backend
    (and the init_fn every other backend shares)."""
    from repro.core.algorithm1 import RoundOutput, hinge_loss_and_grad
    from repro.core import prox

    m = spec.nodes
    n = spec.dim
    if n is None:
        raise ValueError("RunSpec.dim is required by repro.api.run")
    loss_and_grad = spec.loss_and_grad or hinge_loss_and_grad

    if engine == "sim":
        alg = spec.build_simulator()

        def chunk_fn(state, xs, ys):
            return jax.lax.scan(alg.round, state, (xs, ys))

        return chunk_fn, alg.init

    if engine == "dist":
        gdp = spec.build_distributed()

        def chunk_fn(state, xs, ys):
            def body(st, batch):
                x, y = batch
                w = gdp.primal(st)["w"]
                loss, grad = loss_and_grad(w, x, y)
                correct = (jnp.sign(jnp.einsum("mn,mn->m", w, x)) == y
                           ).astype(jnp.float32)
                st, _ = gdp.update(st, {"w": grad})
                # identical metric algebra to Algorithm1.round, so the two
                # engines' trajectories compare element-for-element (and the
                # multiply+reduce margin lowers the same under a seed vmap)
                w_bar = jnp.mean(w, axis=0, keepdims=True)
                wb_loss = jnp.mean(jnp.maximum(
                    1.0 - y * jnp.sum(w_bar * x, axis=-1), 0.0))
                out = RoundOutput(loss=loss, w_bar_loss=wb_loss,
                                  sparsity=prox.sparsity(w), correct=correct)
                return st, out
            return jax.lax.scan(body, state, (xs, ys))

        def init_fn(key):
            return gdp.init({"w": jnp.zeros((m, n), jnp.float32)}, key)

        return chunk_fn, init_fn

    raise ValueError(f"unknown engine {engine!r}; expected 'sim' or 'dist'")


def make_chunk_fn(spec: RunSpec, engine: str) -> tuple[Callable, Any]:
    """(chunk_fn, initial_state) for one engine — `make_chunk_program` with
    the state built from ``spec.seed``. Exposed so `launch.dryrun` can
    lower/compile the exact program `run` executes."""
    chunk_fn, init_fn = make_chunk_program(spec, engine)
    return chunk_fn, init_fn(jax.random.PRNGKey(spec.seed))


def _final_primal(spec: RunSpec, engine: str, state) -> np.ndarray:
    """(m, n) primal parameters from the final engine state — the same
    schedule context for both engines (Algorithm1.final_params convention)."""
    rule = spec.resolve_local_rule()
    ctx = spec.omd_config().step_context(state.t)
    theta = state.theta if engine == "sim" else state.theta["w"]
    return np.asarray(rule.primal(theta, ctx))


def _boundaries(start: int, T: int, chunk_rounds: int,
                checkpoint_every: int | None) -> list[int]:
    """Chunk split points: every chunk_rounds, also landing on every
    checkpoint_every multiple so checkpoints capture exact round states."""
    ts = [start]
    t = start
    while t < T:
        nxt = t + chunk_rounds
        if checkpoint_every:
            nxt = min(nxt, ((t // checkpoint_every) + 1) * checkpoint_every)
        ts.append(min(nxt, T))
        t = ts[-1]
    return ts


_WSTAR_CACHE: dict = {}


def _regret(stream, w_bar_loss: np.ndarray, xs: np.ndarray, ys: np.ndarray,
            m: int) -> np.ndarray:
    from repro.core.regret import best_fixed_hinge, cumulative_regret
    cache_key = (stream, xs.shape)
    try:
        w_star = _WSTAR_CACHE.get(cache_key)
    except TypeError:                      # unhashable custom stream
        cache_key, w_star = None, None
    if w_star is None:
        w_star = best_fixed_hinge(jnp.asarray(xs), jnp.asarray(ys))
        if cache_key is not None:
            _WSTAR_CACHE[cache_key] = w_star
    return cumulative_regret(jnp.asarray(w_bar_loss), jnp.asarray(xs),
                             jnp.asarray(ys), m, w_star=w_star)


def run(spec: RunSpec | None, engine: str = "sim", *,
        exec: ExecConfig | None = None,
        horizon: int | None = None,
        on_chunk: Callable | None = None,
        step_fn: Callable | None = None,
        state: Any = None,
        batches: Iterator | None = None,
        **legacy: Any) -> RunResult:
    """Drive one run end-to-end and return a RunResult.

    Execution knobs (chunking, checkpointing, logging, meshes, telemetry)
    travel as ``exec=ExecConfig(...)`` — see `repro.api.exec_config` for
    every field and the legacy-kwarg migration table. The old keyword
    arguments (``chunk_rounds=``, ``checkpoint_every=``, ...) still work
    via ``**legacy`` with a once-per-process DeprecationWarning.

    Stream mode (default): resolves ``spec.stream`` and scans the chosen
    engine over the horizon in jitted chunks. ``checkpoint_every`` saves the
    engine state every N rounds into ``checkpoint_dir``; ``resume=True``
    restores the latest checkpoint and continues bit-identically (streams
    are keyed per absolute round, so the data after resume is unchanged).
    ``warmup=True`` compiles the first chunk outside the timed region so
    rounds_per_sec measures steady-state execution.

    ``on_chunk(round_end, eng_state, accountant)`` fires after every
    completed chunk with the ABSOLUTE round it ended on, the engine state at
    that round (host-synchronized — safe to publish or serialize) and the
    live accountant; returning a truthy value stops the run early at that
    chunk boundary (trajectories and the eps ledger cover only the completed
    rounds). This is the snapshot-publication hook the serving layer
    (`repro.serve`) hangs its background trainer on — a published snapshot
    at round r is bit-identical to a fresh ``run(spec, horizon=r)`` because
    streams are keyed per absolute round and chunking never changes the
    per-round math.

    ``node_devices=`` (or a prebuilt ``node_mesh=`` with a "node" axis)
    SHARDS the node axis itself across devices: the spec's topology is
    lowered to its sparse edge-list form and the whole per-chunk scan runs
    under `shard_map` with a ppermute halo exchange for cross-shard edges
    (see `repro.api.shard_node`). State entering/leaving each chunk stays
    global and unpadded, so checkpoints interchange with any device count
    (and with the unsharded path). The per-round noise is bit-identical to
    the dense engines; only float32 reduction order differs.

    Custom mode (``step_fn=``): drives ``state, metrics = step_fn(state,
    next(batches))`` for ``horizon`` steps with the same tracking /
    logging / accounting / checkpointing — the loop `launch.train` uses, so
    the train CLI and the benchmarks cannot diverge.

    ``obs=`` takes a `repro.obs.Telemetry` (default: the ambient
    ``repro.obs.active()``, disabled unless ``repro.obs.enable()`` ran).
    Each chunk-loop iteration runs in phase spans (``run.stream``,
    ``run.chunk`` holding ``run.dispatch`` and ``run.wait``,
    ``run.account``, ``run.fetch``, ``run.log``, ``run.checkpoint``,
    ``run.on_chunk``); each span is also a ``jax.profiler``
    annotation, which a running profiler records whether or not telemetry
    is enabled (see `repro.obs.trace`). When enabled, the runner also keeps
    the compile / chunk-loop / regret spans in memory, publishes
    ``run.rounds`` / ``run.chunk_seconds`` / ``run.eps_total`` (and fault connectivity) into the metrics registry,
    streams ``run_start`` / ``chunk`` / ``checkpoint`` / ``run_end`` events,
    and — with ``Telemetry(cost=True)`` — records the predicted-vs-measured
    chunk cost under ``result.metrics['obs']['cost']``. Telemetry is strictly
    host-side: a telemetry-on run is bit-identical to a telemetry-off run
    (gated as ``obs_off_identical`` in BENCH_obs.json).
    """
    cfg = resolve_exec(exec, legacy, caller="run")
    if step_fn is not None:
        return _run_custom(spec, engine, step_fn=step_fn, state=state,
                           batches=batches, horizon=horizon,
                           log_path=cfg.log_path, print_every=cfg.print_every,
                           checkpoint_every=cfg.checkpoint_every,
                           checkpoint_dir=cfg.checkpoint_dir)
    if spec is None:
        raise ValueError("run() needs a RunSpec (or step_fn= for custom mode)")

    stream = spec.resolve_stream()
    T = horizon or spec.horizon or stream.rounds
    m = spec.nodes

    mech = spec.resolve_mechanism()
    # a custom stream that does not DECLARE disjoint rounds gets the
    # pessimistic sequential composition — never overstate a DP guarantee
    accountant = PrivacyAccountant(
        eps_per_round=spec.eps if mech.is_private else math.inf,
        disjoint_streams=getattr(stream, "disjoint", False))

    # repro.faults: one resolved faulty mixer for metrics + accounting — the
    # fault pattern is seeded by FaultSpec.seed, so this instance agrees
    # bit-for-bit with the one baked into the chunk program
    fault_mixer = (spec.resolve_mixer()
                   if getattr(spec, "faults", None) is not None else None)
    fault_sched = getattr(fault_mixer, "schedule", None)

    tel = cfg.obs if cfg.obs is not None else obslib.active()
    run_id = tel.new_run_id() if tel.enabled else None

    nmesh = None
    if cfg.node_devices is not None or cfg.node_mesh is not None:
        from repro.api.shard_node import resolve_node_mesh
        nmesh = resolve_node_mesh(cfg.node_devices, cfg.node_mesh)
    if nmesh is None:
        chunk_fn, init_state = make_chunk_fn(spec, engine)
    else:
        from repro.api.shard_node import make_node_chunk_fn
        chunk_fn, init_fn = make_node_chunk_fn(spec, engine, nmesh)
        init_state = init_fn(jax.random.PRNGKey(spec.seed))
    chunk_jit = jax.jit(chunk_fn)

    start = 0
    eng_state = init_state
    if cfg.resume:
        if not cfg.checkpoint_dir:
            raise ValueError("resume=True needs checkpoint_dir=")
        found = latest_step(cfg.checkpoint_dir)
        if found is not None:
            eng_state = restore_checkpoint(cfg.checkpoint_dir, init_state,
                                           step=found)
            start = found
    accountant.rounds = start

    bounds = _boundaries(start, T, cfg.chunk_rounds, cfg.checkpoint_every)
    logger = CSVLogger(cfg.log_path) if cfg.log_path else None

    first_chunk = None
    if cfg.warmup and len(bounds) > 1:
        first_chunk = stream.chunk(bounds[0], bounds[1])
        with tel.span("run.compile", engine=engine, run_id=run_id):
            jax.block_until_ready(chunk_jit(eng_state, *first_chunk)[0].theta)

    chunk_cost = None
    if tel.cost_enabled and len(bounds) > 1:
        # one extra lower/compile of the exact chunk program, BEFORE the
        # timed loop (a cache hit when warmup already compiled it), so the
        # cost loop never leaks into steady-state timing
        cxs, cys = (first_chunk if first_chunk is not None
                    else stream.chunk(bounds[0], bounds[1]))
        chunk_cost = obslib.analyze_chunk(chunk_jit, eng_state, cxs, cys,
                                          model=tel.cost_model)

    if tel.enabled:
        tel.emit("run_start", run_id=run_id, kind="run", engine=engine,
                 stream=(spec.stream if isinstance(spec.stream, str)
                         else type(stream).__name__),
                 nodes=m, dim=spec.dim, horizon=T, start_round=start)

    losses, wb_losses, sparsities, corrects = [], [], [], []
    xs_all, ys_all = [], []
    done_to = start
    t0 = time.time()
    with tel.profile():
        # every phase of an iteration sits in a span (and so in a profiler
        # annotation), so a device-idle gap between two chunk programs can
        # be laid to what the host was doing in it
        for a, b in zip(bounds[:-1], bounds[1:]):
            with tel.span("run.stream"):
                if a == bounds[0] and first_chunk is not None:
                    xs, ys = first_chunk   # don't regenerate the warmup chunk
                else:
                    xs, ys = stream.chunk(a, b)
            with tel.span("run.chunk", round_start=a, round_end=b) as sp:
                with tel.span("run.dispatch"):
                    eng_state, outs = chunk_jit(eng_state, xs, ys)
                # block on the STATE too, not just the metric outputs — the
                # timed region must cover the whole round computation, and
                # on_chunk consumers (snapshot publication) need a finished
                # state
                with tel.span("run.wait"):
                    jax.block_until_ready((eng_state, outs))
            with tel.span("run.account"):
                if fault_sched is not None and fault_sched.has_crashes:
                    # crashed rounds release no noised broadcast — don't
                    # charge them
                    accountant.step(
                        b - a, participation=fault_sched.participation(a, b))
                else:
                    accountant.step(b - a)
                done_to = b
                if tel.enabled:
                    secs = sp.duration_s
                    eps_now = accountant.guarantee_at(b)
                    tel.metrics.counter("run.rounds").inc(b - a)
                    tel.metrics.histogram("run.chunk_seconds").observe(secs)
                    tel.metrics.gauge("run.eps_total").set(eps_now)
                    if chunk_cost is not None:
                        chunk_cost.record(secs)
                    tel.emit("chunk", run_id=run_id, round_start=a,
                             round_end=b, seconds=secs,
                             rounds_per_sec=((b - a) / secs if secs > 0
                                             else None),
                             eps=eps_now)
            with tel.span("run.fetch"):
                losses.append(np.asarray(outs.loss))
                wb_losses.append(np.asarray(outs.w_bar_loss))
                sparsities.append(np.asarray(outs.sparsity))
                corrects.append(np.asarray(outs.correct))
                if cfg.compute_regret:
                    xs_all.append(np.asarray(xs))
                    ys_all.append(np.asarray(ys))
            if logger:
                with tel.span("run.log"):
                    for i, t in enumerate(range(a, b)):
                        logger.log(t, {
                            "loss": float(losses[-1][i].mean()),
                            "w_bar_loss": float(wb_losses[-1][i]),
                            "sparsity": float(sparsities[-1][i]),
                            "accuracy": float(corrects[-1][i].mean()),
                            "eps": accountant.guarantee_at(t + 1),
                        })
            if (cfg.checkpoint_every and cfg.checkpoint_dir
                    and b % cfg.checkpoint_every == 0):
                with tel.span("run.checkpoint", step=b):
                    save_checkpoint(cfg.checkpoint_dir, b, eng_state)
                    tel.emit("checkpoint", run_id=run_id, step=b)
            if on_chunk is not None:
                with tel.span("run.on_chunk"):
                    stop = on_chunk(b, eng_state, accountant)
                if stop:
                    break
    wall = time.time() - t0
    T = done_to                 # < requested horizon iff on_chunk stopped early
    if logger:
        logger.close()

    correct = np.concatenate(corrects) if corrects else np.zeros((0, m))
    w_bar_loss = np.concatenate(wb_losses) if wb_losses else np.zeros((0,))
    tail = max(1, int(correct.shape[0] * 0.2)) if correct.size else 1
    regret = None
    if cfg.compute_regret and start == 0 and xs_all:
        with tel.span("run.regret", rounds=int(w_bar_loss.shape[0])):
            regret = _regret(stream, w_bar_loss, np.concatenate(xs_all),
                             np.concatenate(ys_all), m)

    done = T - start
    result = RunResult(
        engine=engine,
        rounds=T,
        start_round=start,
        wall_clock=wall,
        rounds_per_sec=(done / wall) if wall > 0 else float("inf"),
        stream=(spec.stream if isinstance(spec.stream, str)
                else type(stream).__name__),
        eps_ledger=np.asarray(accountant.ledger(T)[start:]),
        privacy=accountant.summary(),
        loss=np.concatenate(losses) if losses else None,
        w_bar_loss=w_bar_loss if len(w_bar_loss) else None,
        correct=correct if correct.size else None,
        sparsity=np.concatenate(sparsities) if sparsities else None,
        regret=None if regret is None else np.asarray(regret),
        accuracy=float(correct[-tail:].mean()) if correct.size else None,
        final_w=_final_primal(spec, engine, eng_state),
        final_state=eng_state,
    )
    result.metrics = result.summary()
    if fault_mixer is not None and done > 0:
        conn = np.asarray(fault_mixer.connectivity(T))[start:]
        result.connectivity = conn
        result.metrics["faults"] = _fault_metrics(spec, fault_sched, conn)
        if tel.enabled:
            tel.metrics.gauge("faults.mean_connectivity").set(
                result.metrics["faults"]["mean_connectivity"])
    if tel.enabled:
        obs_info: dict[str, Any] = {"run_id": run_id}
        if chunk_cost is not None:
            cs = chunk_cost.summary()
            obs_info["cost"] = cs
            tel.emit("chunk_cost", run_id=run_id,
                     **{k: cs[k] for k in ("predicted_s", "measured_mean_s",
                                           "error_ratio", "flops",
                                           "hbm_bytes")})
        result.metrics["obs"] = obs_info
        tel.emit("run_end", run_id=run_id, rounds=T, wall_clock_s=wall,
                 rounds_per_sec=result.rounds_per_sec,
                 accuracy=result.accuracy,
                 eps_total=result.privacy.get("eps_total"))
    return result


def _fault_metrics(spec: RunSpec, fault_sched, conn: np.ndarray) -> dict:
    """Per-run degradation summary attached as ``metrics['faults']``."""
    name = (spec.faults if isinstance(spec.faults, str)
            else getattr(spec.faults, "name", "faults"))
    return {
        "spec": name,
        "mean_connectivity": float(conn.mean()),
        "min_connectivity": float(conn.min()),
        "crash_windows": len(getattr(fault_sched, "crash_windows", ()) or ()),
        "partitions": len(getattr(fault_sched, "partitions", ()) or ()),
    }


# -- vectorized multi-seed execution ----------------------------------------

def _config_eq(a: Any, b: Any) -> bool:
    """Structural equality for resolved protocol stages (mixers etc.)."""
    if a is b:
        return True
    if type(a) is not type(b):
        return False
    if isinstance(a, (np.ndarray, jnp.ndarray, np.generic)):
        return (np.shape(a) == np.shape(b)
                and bool(np.array_equal(np.asarray(a), np.asarray(b))))
    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        return all(_config_eq(getattr(a, f.name), getattr(b, f.name))
                   for f in dataclasses.fields(a))
    if isinstance(a, dict):
        return (a.keys() == b.keys()
                and all(_config_eq(v, b[k]) for k, v in a.items()))
    if isinstance(a, (list, tuple)):
        return (len(a) == len(b)
                and all(_config_eq(x, y) for x, y in zip(a, b)))
    if hasattr(a, "__dict__") and not callable(a):
        return _config_eq(vars(a), vars(b))
    try:
        return bool(a == b)
    except Exception:
        return False


def seed_vectorizable(spec: RunSpec, seeds) -> bool:
    """True when a seed batch can share ONE compiled chunk program.

    The vmapped path bakes the resolved mixer (and the rest of the stage
    pipeline) into the program once, from the first seed; only the PRNG key
    and the stream data vary per seed. Seeded topologies ('random',
    'time_varying', per-edge `delay_dist` draws) resolve to DIFFERENT mixing
    matrices per seed, so they must fall back to sequential `run()` calls —
    `repro.sweep` consults this predicate to pick the path automatically.
    """
    seeds = list(seeds)
    if len(seeds) <= 1:
        return True
    base = spec.replace(seed=seeds[0]).resolve_mixer()
    return all(_config_eq(spec.replace(seed=s).resolve_mixer(), base)
               for s in seeds[1:])


def _index_tree(tree: Any, i: int) -> Any:
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _pad_tree(tree: Any, pad: int) -> Any:
    """Grow every leaf's leading (seed) axis by ``pad`` copies of its last
    entry. Pad seeds are throwaway duplicates — `_unpad_tree` masks them out
    of every aggregate before results are read."""
    if pad == 0:
        return tree
    return jax.tree_util.tree_map(
        lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)]), tree)


def _unpad_tree(tree: Any, n: int) -> Any:
    return jax.tree_util.tree_map(lambda x: x[:n], tree)


def _resolve_seed_mesh(devices: int | str | None, mesh: Any):
    """The ("seed",) mesh to shard the batch over, or None for plain vmap.

    ``devices=None`` keeps the single-device vmap path; ``"auto"`` takes
    every local device (falling back to vmap on a 1-device host); an int
    asks for exactly that many. A prebuilt mesh must carry a "seed" axis.
    """
    if mesh is not None:
        if "seed" not in mesh.axis_names:
            raise ValueError(
                f"run_batch needs a mesh with a 'seed' axis, got axes "
                f"{tuple(mesh.axis_names)}")
        return mesh if int(mesh.shape["seed"]) > 1 else None
    if devices is None:
        return None
    from repro.launch.mesh import seed_mesh
    return seed_mesh(devices)


def run_batch(spec: RunSpec, seeds, engine: str = "sim", *,
              exec: ExecConfig | None = None,
              horizon: int | None = None,
              **legacy: Any) -> list[RunResult]:
    """Run one config under S seeds as ONE vmapped program; S RunResults.

    Execution knobs travel as ``exec=ExecConfig(...)`` exactly like `run`
    (legacy kwargs keep working with a once-per-process deprecation
    warning); ``devices=``/``mesh=``/``check_vectorizable=`` are the
    batch-only ExecConfig fields.

    The innermost (seed) axis is vectorized: per-seed engine states are
    stacked into a leading axis of size S, the per-seed stream chunks are
    stacked the same way, and `jax.vmap` of the runner's per-chunk `lax.scan`
    drives all S trajectories in a single compiled pass — one compilation
    and roughly one memory-bound sweep instead of S sequential `run()` calls.
    Each returned RunResult is bit-identical to the corresponding
    ``run(spec.replace(seed=s), engine)`` (same stream chunks, same PRNG
    keys, same scan — the seed-vmap equivalence tests hold this to the bit),
    with ``wall_clock`` amortized as batch wall / S and the batch totals
    under ``metrics["batch"]``.

    ``devices=`` (or a prebuilt ``mesh=`` with a "seed" axis) additionally
    SHARDS the vmapped seed axis across local devices with `shard_map` over
    a 1-D ``("seed",)`` mesh: S is padded up to a multiple of the device
    count D with throwaway duplicate seeds, each device runs the same vmapped
    chunk program over its S/D block, and the pad seeds are sliced out of
    every trajectory, checkpoint and aggregate. Seeds are independent private
    runs, so the sharded results stay bit-identical to the single-device
    vmap (and to sequential `run()`) — noise, delay rings and resume
    included. On a TPU that holds for backend="pallas"; XLA may order the
    reference backend's reductions differently for S seeds on one device
    than for S/D per device, a last-bit difference (docs/sweeps.md). ``devices="auto"`` uses `jax.local_device_count()` and falls
    back to plain vmap on a 1-device host.

    ``node_devices=`` composes node sharding with the seed batch into a 2-D
    ``("seed", "node")`` grid (``devices`` then counts SEED rows, default 1;
    a prebuilt ``mesh=`` may carry both axes): each seed row runs the
    node-sharded sparse chunk program of `repro.api.shard_node`, vmapped
    over its seed block inside one shard_map. Node padding lives inside the
    chunk program, so the seed pad-and-mask logic and checkpoints here are
    unchanged.

    Checkpoints (``checkpoint_every``/``checkpoint_dir``/``resume``) store
    the STACKED state gathered to host and stripped of pad seeds, so a run
    saved under one device count resumes bit-identically under any other
    (4 devices -> 1, 1 -> 8, ...).

    ``obs=`` instruments the batch exactly like `run` (default: the ambient
    `repro.obs.active`): ``run_batch.compile`` / ``run_batch.chunk``
    spans, ``run_batch.*`` metrics, one shared ``run_id`` across the batch's
    events and RunResults, and — with ``Telemetry(cost=True)`` — the
    predicted-vs-measured cost of the whole S-seed chunk program. Host-side
    only; telemetry-on results stay bit-identical to telemetry-off.
    Raises ValueError when the spec's resolved stages depend on the seed
    (see `seed_vectorizable`) — callers like `repro.sweep` fall back to
    sequential per-seed runs in that case.
    """
    cfg = resolve_exec(exec, legacy, caller="run_batch")
    devices, mesh = cfg.devices, cfg.mesh
    node_devices = cfg.node_devices
    seeds = [int(s) for s in seeds]
    if not seeds:
        raise ValueError("run_batch needs at least one seed")
    # check_vectorizable=False skips the per-seed mixer resolutions when the
    # caller (repro.sweep) already ran seed_vectorizable on this spec
    if cfg.check_vectorizable and not seed_vectorizable(spec, seeds):
        raise ValueError(
            "the resolved mixer depends on RunSpec.seed (seeded topology or "
            "delay_dist); a vmapped batch would share one mixing matrix "
            "across seeds — run sequentially per seed instead (repro.sweep "
            "does this fallback automatically)")

    specs = [spec.replace(seed=s) for s in seeds]
    base = specs[0]
    streams = [s.resolve_stream() for s in specs]
    T = horizon or base.horizon or streams[0].rounds
    m = spec.nodes
    S = len(seeds)

    mech = base.resolve_mechanism()
    accountant = PrivacyAccountant(
        eps_per_round=spec.eps if mech.is_private else math.inf,
        disjoint_streams=getattr(streams[0], "disjoint", False))

    # FaultSpec.seed is independent of RunSpec.seed, so every seed in the
    # batch runs under the SAME fault pattern (it's part of the scenario)
    fault_mixer = (base.resolve_mixer()
                   if getattr(base, "faults", None) is not None else None)
    fault_sched = getattr(fault_mixer, "schedule", None)

    tel = cfg.obs if cfg.obs is not None else obslib.active()
    run_id = tel.new_run_id() if tel.enabled else None

    chunk_fn, init_fn = make_chunk_program(base, engine)
    init_states = [init_fn(jax.random.PRNGKey(s)) for s in seeds]
    batched_init = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *init_states)

    node_grid = None
    if node_devices is not None or (
            mesh is not None and "node" in getattr(mesh, "axis_names", ())):
        if mesh is not None:
            if "seed" not in mesh.axis_names:
                raise ValueError(
                    "run_batch node sharding needs a ('seed','node') mesh")
            node_grid = mesh
        else:
            from repro.launch.mesh import seed_node_mesh
            seed_dev = 1 if devices in (None, "auto") else int(devices)
            node_grid = seed_node_mesh(seed_dev, node_devices)
        mesh = node_grid        # _place shards the seed axis of this grid

    if node_grid is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.api.shard_node import make_node_chunk_fn
        D = int(node_grid.shape["seed"])
        pad = (-S) % D
        sharding = NamedSharding(node_grid, PartitionSpec("seed"))
        # the node-sharded chunk program vmaps the seed axis inside its own
        # ("seed","node") shard_map; the seed pad-and-mask stays out here
        chunk_jit = jax.jit(make_node_chunk_fn(base, engine, node_grid,
                                               batched=True)[0])
    else:
        mesh = _resolve_seed_mesh(devices, mesh)
        D = int(mesh.shape["seed"]) if mesh is not None else 1
        pad = (-S) % D
        if mesh is None:
            sharding = None
            chunk_jit = jax.jit(jax.vmap(chunk_fn))
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            pspec = PartitionSpec("seed")
            sharding = NamedSharding(mesh, pspec)
            # each device runs the SAME vmapped chunk program over its S/D
            # block of seeds; no collectives cross the blocks, so per-seed
            # trajectories cannot differ from the single-device vmap
            chunk_jit = jax.jit(jax.shard_map(
                jax.vmap(chunk_fn), mesh=mesh,
                in_specs=(pspec, pspec, pspec), out_specs=(pspec, pspec),
                check_vma=False))

    def _place(tree):
        """Pad the seed axis to S + pad and lay it out over the mesh."""
        if mesh is None:
            return tree
        return jax.device_put(_pad_tree(tree, pad), sharding)

    start = 0
    eng_state = _place(batched_init)
    if cfg.resume:
        if not cfg.checkpoint_dir:
            raise ValueError("resume=True needs checkpoint_dir=")
        found = latest_step(cfg.checkpoint_dir)
        if found is not None:
            # checkpoints hold the UNPADDED (S, ...) host state, so a run
            # saved under any device count restores under this one
            eng_state = _place(restore_checkpoint(cfg.checkpoint_dir,
                                                  batched_init, step=found))
            start = found
    accountant.rounds = start

    def stacked_chunk(a: int, b: int):
        pairs = [st.chunk(a, b) for st in streams]
        return _place((jnp.stack([p[0] for p in pairs]),
                       jnp.stack([p[1] for p in pairs])))

    bounds = _boundaries(start, T, cfg.chunk_rounds, cfg.checkpoint_every)

    first_chunk = None
    if cfg.warmup and len(bounds) > 1:
        first_chunk = stacked_chunk(bounds[0], bounds[1])
        with tel.span("run_batch.compile", engine=engine, seeds=S,
                      run_id=run_id):
            jax.block_until_ready(jax.tree_util.tree_leaves(
                chunk_jit(eng_state, *first_chunk)[0])[0])

    chunk_cost = None
    if tel.cost_enabled and len(bounds) > 1:
        # the WHOLE S-seed chunk program's cost (all seeds in one pass),
        # analyzed outside the timed loop — cache hit after warmup
        cxs, cys = (first_chunk if first_chunk is not None
                    else stacked_chunk(bounds[0], bounds[1]))
        chunk_cost = obslib.analyze_chunk(chunk_jit, eng_state, cxs, cys,
                                          model=tel.cost_model)

    if tel.enabled:
        tel.emit("run_start", run_id=run_id, kind="run_batch", engine=engine,
                 stream=(spec.stream if isinstance(spec.stream, str)
                         else type(streams[0]).__name__),
                 nodes=m, dim=spec.dim, horizon=T, start_round=start,
                 seeds=seeds, devices=D)

    losses, wb_losses, sparsities, corrects = [], [], [], []
    xs_all, ys_all = [], []
    t0 = time.time()
    with tel.profile():
        # the same phase spans as run()'s loop, under run_batch.* names
        for a, b in zip(bounds[:-1], bounds[1:]):
            with tel.span("run_batch.stream"):
                if a == bounds[0] and first_chunk is not None:
                    xs, ys = first_chunk
                else:
                    xs, ys = stacked_chunk(a, b)
            with tel.span("run_batch.chunk", round_start=a, round_end=b,
                          seeds=S) as sp:
                with tel.span("run_batch.dispatch"):
                    eng_state, outs = chunk_jit(eng_state, xs, ys)
                # block on state + outputs so the timed region measures the
                # whole round computation, not just the dispatch of the
                # metric arrays
                with tel.span("run_batch.wait"):
                    jax.block_until_ready((eng_state, outs))
            with tel.span("run_batch.account"):
                if fault_sched is not None and fault_sched.has_crashes:
                    accountant.step(
                        b - a, participation=fault_sched.participation(a, b))
                else:
                    accountant.step(b - a)
                if tel.enabled:
                    secs = sp.duration_s
                    eps_now = accountant.guarantee_at(b)
                    tel.metrics.counter("run_batch.rounds").inc(b - a)
                    tel.metrics.histogram(
                        "run_batch.chunk_seconds").observe(secs)
                    tel.metrics.gauge("run_batch.eps_total").set(eps_now)
                    if chunk_cost is not None:
                        chunk_cost.record(secs)
                    tel.emit("chunk", run_id=run_id, round_start=a,
                             round_end=b, seconds=secs,
                             rounds_per_sec=((b - a) / secs if secs > 0
                                             else None),
                             eps=eps_now)
            with tel.span("run_batch.fetch"):
                # [:S] masks the pad seeds (duplicates of the last real
                # seed) out of every recorded trajectory; a no-op on the
                # unsharded path
                losses.append(np.asarray(outs.loss)[:S])           # (S, C, m)
                wb_losses.append(np.asarray(outs.w_bar_loss)[:S])  # (S, C)
                sparsities.append(np.asarray(outs.sparsity)[:S])
                corrects.append(np.asarray(outs.correct)[:S])
                if cfg.compute_regret:
                    xs_all.append(np.asarray(xs)[:S])
                    ys_all.append(np.asarray(ys)[:S])
            if (cfg.checkpoint_every and cfg.checkpoint_dir
                    and b % cfg.checkpoint_every == 0):
                with tel.span("run_batch.checkpoint", step=b):
                    save_checkpoint(cfg.checkpoint_dir, b,
                                    _unpad_tree(eng_state, S))
                    tel.emit("checkpoint", run_id=run_id, step=b)
    wall = time.time() - t0
    eng_state = _unpad_tree(eng_state, S)

    # a fully-resumed batch (start >= T) executes no chunks; degrade to
    # empty trajectories exactly like run() does instead of crashing
    loss = (np.concatenate(losses, axis=1) if losses
            else np.zeros((S, 0, m)))             # (S, T', m)
    w_bar_loss = (np.concatenate(wb_losses, axis=1) if wb_losses
                  else np.zeros((S, 0)))
    sparsity = (np.concatenate(sparsities, axis=1) if sparsities
                else np.zeros((S, 0)))
    correct = (np.concatenate(corrects, axis=1) if corrects
               else np.zeros((S, 0, m)))
    done = T - start
    tail = max(1, int(correct.shape[1] * 0.2)) if correct.size else 1
    eps_ledger = np.asarray(accountant.ledger(T)[start:])
    batch_info = {"seeds": seeds, "wall_clock_s": wall,
                  "devices": D, "pad_seeds": pad,
                  "seed_rounds_per_sec": (S * done / wall if wall > 0
                                          else float("inf"))}
    conn = faults_info = None
    if fault_mixer is not None and done > 0:
        conn = np.asarray(fault_mixer.connectivity(T))[start:]
        faults_info = _fault_metrics(base, fault_sched, conn)

    obs_info = None
    if tel.enabled:
        if fault_mixer is not None and conn is not None:
            tel.metrics.gauge("faults.mean_connectivity").set(
                faults_info["mean_connectivity"])
        obs_info = {"run_id": run_id}
        if chunk_cost is not None:
            cs = chunk_cost.summary()
            obs_info["cost"] = cs
            tel.emit("chunk_cost", run_id=run_id,
                     **{k: cs[k] for k in ("predicted_s", "measured_mean_s",
                                           "error_ratio", "flops",
                                           "hbm_bytes")})
        tel.emit("run_end", run_id=run_id, rounds=T, wall_clock_s=wall,
                 rounds_per_sec=(S * done / wall if wall > 0 else None),
                 eps_total=accountant.summary().get("eps_total"),
                 seeds=seeds)

    results = []
    for i, (s, st) in enumerate(zip(seeds, streams)):
        regret = None
        if cfg.compute_regret and start == 0 and xs_all:
            with tel.span("run_batch.regret", seed=s):
                regret = _regret(st, w_bar_loss[i],
                                 np.concatenate([x[i] for x in xs_all]),
                                 np.concatenate([y[i] for y in ys_all]), m)
        res = RunResult(
            engine=engine,
            rounds=T,
            start_round=start,
            wall_clock=wall / S,
            rounds_per_sec=(S * done / wall) if wall > 0 else float("inf"),
            stream=(spec.stream if isinstance(spec.stream, str)
                    else type(st).__name__),
            eps_ledger=eps_ledger.copy(),
            privacy=accountant.summary(),
            loss=loss[i] if loss.size else None,
            w_bar_loss=w_bar_loss[i] if w_bar_loss.size else None,
            correct=correct[i] if correct.size else None,
            sparsity=sparsity[i] if sparsity.size else None,
            regret=None if regret is None else np.asarray(regret),
            connectivity=None if conn is None else conn.copy(),
            accuracy=float(correct[i, -tail:].mean()) if correct.size else None,
            final_w=_final_primal(specs[i], engine, _index_tree(eng_state, i)),
            final_state=_index_tree(eng_state, i),
        )
        res.metrics = res.summary()
        res.metrics["batch"] = dict(batch_info)
        if faults_info is not None:
            res.metrics["faults"] = dict(faults_info)
        if obs_info is not None:
            res.metrics["obs"] = dict(obs_info)
        results.append(res)
    return results


def _run_custom(spec, engine, *, step_fn, state, batches, horizon,
                log_path, print_every, checkpoint_every,
                checkpoint_dir) -> RunResult:
    if horizon is None:
        raise ValueError("custom mode needs horizon= (number of steps)")
    accountant = None
    if spec is not None:
        mech = spec.resolve_mechanism()
        accountant = PrivacyAccountant(
            eps_per_round=spec.eps if mech.is_private else math.inf)
    tracker = MetricTracker()
    logger = CSVLogger(log_path) if log_path else None
    history = []
    t0 = time.time()
    for i in range(horizon):
        batch = next(batches)
        state, metrics = step_fn(state, batch)
        metrics = {k: float(v) for k, v in metrics.items()}
        tracker.update(metrics)
        history.append(metrics)
        if accountant is not None:
            accountant.step()
        if logger:
            logger.log(i, metrics)
        if checkpoint_every and checkpoint_dir and (i + 1) % checkpoint_every == 0:
            save_checkpoint(checkpoint_dir, i + 1, state)
        if print_every and (i % print_every == 0 or i == horizon - 1):
            means = tracker.means()
            print(f"step {i:4d} loss={means.get('loss', 0):.4f} "
                  f"ce={means.get('ce', 0):.4f} "
                  f"sparsity={means.get('theta_sparsity', 0):.3f} "
                  f"({(time.time() - t0) / (i + 1):.2f}s/step)")
    wall = time.time() - t0
    if logger:
        logger.close()
    return RunResult(
        engine=engine,
        rounds=horizon,
        wall_clock=wall,
        rounds_per_sec=(horizon / wall) if wall > 0 else float("inf"),
        eps_ledger=(None if accountant is None
                    else np.asarray(accountant.ledger())),
        privacy={} if accountant is None else accountant.summary(),
        final_state=state,
        history=history,
        metrics=tracker.means(),
    )
