#!/usr/bin/env python3
"""Smoke run of the private gossip learner on a TPU, at the paper's §V size.

The paper's §V setting (`repro.configs.social_linear`): m=64 data-center
nodes on a ring, n=10,000 features, 100,000 samples (1,562 rounds), Laplace
noise with eps=1 and coordinate calibration, lam=1e-3, the `social_sparse`
stream. All data comes from ``--seed``. Everything runs in this one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded paths, on four chips

One chip, in order:
  1. device check: the platform must be ``tpu`` (no CPU fallback);
  2. kernels: the §V pallas chunk program is compiled and must contain
     ``tpu_custom_call``, so the kernels are not interpreted;
  3. §V runs through `repro.api.run`: pallas/sim with `ExecConfig`
     defaults (regret on), pallas/dist, and reference/sim with its XLA
     matmuls at "highest" precision (the float32 oracle). The eps ledgers
     must be exact, sim and dist bit-identical, the stream data the same
     under both precisions, and pallas within the bounds of
     docs/kernels.md of the reference.

Four chips (``--chips 4``) runs only the sharded paths and what they are
compared with: the §V run node-sharded over 4 chips under both backends
against the one-chip run, and `run_batch` seed-sharded over 4 chips
against the single-device vmap, bit for bit.

Rates and times printed here are smoke readings, not benchmark numbers.
A failed phase makes the exit code non-zero, and only a run in which every
phase passed prints its last line, one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# The bound on every field, pallas vs the float32 reference and node-sharded
# vs one chip (docs/kernels.md "The tolerance contract", and the bound
# tests/test_shard_node.py asserts). For the 0/1 `correct` it means
# identical; for `sparsity`, a share of the m*n = 640,000 entries of w, it
# means at most 3 entries flip across the prox threshold in any round.
ATOL = 5e-6
FIELDS = ("final_w", "loss", "w_bar_loss", "correct", "sparsity")


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {info['platform']!r}")
    if info["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, JAX sees "
                         f"{info['count']}")
    return info


def paper_spec(backend: str, seed: int, **kw):
    """The §V RunSpec, as `benchmarks.common.make_spec(Scale.paper())`."""
    from repro.api import RunSpec
    from repro.configs.social_linear import CONFIG as cfg
    base = dict(nodes=cfg.nodes, dim=cfg.n, mixer=cfg.topology, eps=cfg.eps,
                clip_norm=cfg.L, calibration="coordinate",
                alpha0=cfg.alpha0, schedule=cfg.schedule, lam=cfg.lam,
                horizon=cfg.rounds, stream="social_sparse", seed=seed,
                backend=backend)
    base.update(kw)
    return RunSpec(**base)


def deviations(a, b, what: str) -> None:
    """Print the largest |a - b| of every field, and how often and how far
    sparsity differs; fail when a field is over ATOL."""
    import numpy as np
    dev = {}
    for f in FIELDS:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        check(x.shape == y.shape, f"{f}: shapes {x.shape} vs {y.shape}")
        dev[f] = float(np.abs(x - y).max())
    entries = np.rint(np.abs(a.sparsity - b.sparsity) * a.final_w.size)
    print(f"       {what}: max |diff| {json.dumps(dev)}; sparsity differs "
          f"in {int(np.count_nonzero(entries))} of {entries.size} rounds, "
          f"by at most {int(entries.max())} of {a.final_w.size} entries",
          flush=True)
    over = {f: d for f, d in dev.items() if d > ATOL}
    check(not over, f"{what}: over the {ATOL} bound: {over}")


def identical(a, b) -> bool:
    import numpy as np
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f)))
               for f in FIELDS + ("eps_ledger",))


class Smoke:
    def __init__(self):
        self.failed: list[str] = []

    def phase(self, name: str, fn, *args, **kw):
        """Run one phase; print its wall time; record a failure."""
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kw)
        except Exception as err:                 # noqa: BLE001
            wall = time.perf_counter() - t0
            traceback.print_exc()
            print(f"[FAIL] {name} ({wall:.3f}s): {type(err).__name__}: "
                  f"{err}", flush=True)
            self.failed.append(name)
            return None
        wall = time.perf_counter() - t0
        print(f"[ok]   {name} (smoke reading: {wall:.3f}s wall, compile "
              f"included)", flush=True)
        return out


def run_paper(spec, engine: str, cfg, highest: bool = False):
    import jax
    from repro.api import run
    if highest:
        with jax.default_matmul_precision("highest"):
            res = run(spec, engine=engine, exec=cfg)
    else:
        res = run(spec, engine=engine, exec=cfg)
    print(f"       {spec.backend}/{engine}: {res.rounds} rounds, "
          f"{res.rounds_per_sec:.1f} rounds/s steady (smoke reading), "
          f"accuracy={res.accuracy}", flush=True)
    return res


def kernels_compile(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.api.runner import make_chunk_fn
    from repro.kernels import round_fused as rf
    spec = paper_spec("pallas", seed)
    m_pad, n_pad = rf._pad_rows(spec.nodes), rf._pad_cols(spec.dim)
    for k in rf.KERNELS:
        print(f"       {k}: block {rf.col_block(k, m_pad, n_pad, 512)} "
              f"lanes at ({m_pad}, {n_pad})", flush=True)
    chunk_fn, state = make_chunk_fn(spec, "sim")
    rounds = 8
    xs = jax.ShapeDtypeStruct((rounds, spec.nodes, spec.dim), jnp.float32)
    ys = jax.ShapeDtypeStruct((rounds, spec.nodes), jnp.float32)
    hlo = jax.jit(chunk_fn).lower(state, xs, ys).compile().as_text()
    n = hlo.count("tpu_custom_call")
    print(f"       pallas chunk program: {n} tpu_custom_call sites",
          flush=True)
    check(n > 0, "no tpu_custom_call in the pallas chunk program")


def same_stream(spec) -> None:
    """The stream's chunks are bit-identical at default and at highest
    matmul precision, so both backends learn from the same data."""
    import jax
    import jax.numpy as jnp
    from repro.api.runner import _boundaries
    stream = spec.resolve_stream()
    bounds = _boundaries(0, spec.horizon, 512, None)
    for a, b in zip(bounds[:-1], bounds[1:]):
        xs, ys = stream.chunk(a, b)
        with jax.default_matmul_precision("highest"):
            xs_h, ys_h = stream.chunk(a, b)
        check(bool(jnp.array_equal(xs, xs_h))
              and bool(jnp.array_equal(ys, ys_h)),
              f"stream chunk [{a}, {b}) differs between precisions")
    print("       stream: every chunk bit-identical at default and highest "
          "precision", flush=True)


def compare_one_chip(pal, dist, ref) -> None:
    import numpy as np
    for name, r in (("pallas/dist", dist), ("reference/sim", ref)):
        check(np.array_equal(pal.eps_ledger, r.eps_ledger),
              f"eps_ledger of {name} differs from pallas/sim")
    check(bool(np.all(pal.eps_ledger == 1.0)),
          f"eps_ledger is not flat at eps=1: {pal.eps_ledger[:4]}")
    print(f"       eps_ledger: exact across all three runs, "
          f"eps_total={pal.privacy.get('eps_total')}", flush=True)
    check(identical(pal, dist), "pallas sim and dist are not bit-identical")
    print("       sim == dist: bit-identical (final_w, loss, w_bar_loss, "
          "correct, sparsity, eps_ledger)", flush=True)
    growth = {t: float(np.abs(pal.loss[t - 1] - ref.loss[t - 1]).max())
              for t in (1, 10, 100, 1000, pal.rounds) if t <= pal.rounds}
    print(f"       max|final_w|={float(np.abs(ref.final_w).max())}; "
          f"max|d loss| at round t: {json.dumps(growth)}", flush=True)
    deviations(pal, ref, "pallas vs reference")
    check(pal.regret is not None and pal.regret.shape == (pal.rounds,)
          and bool(np.isfinite(pal.regret).all()),
          "regret missing or not finite")
    print(f"       regret[-1]={float(pal.regret[-1])}", flush=True)


def one_chip(smoke: Smoke, seed: int) -> None:
    from repro.api import ExecConfig
    smoke.phase("kernels compile to Mosaic (tpu_custom_call)",
                kernels_compile, seed)
    pal_spec = paper_spec("pallas", seed)
    ref_spec = paper_spec("reference", seed)
    smoke.phase("stream data identical at both precisions", same_stream,
                pal_spec)
    no_regret = ExecConfig(compute_regret=False)
    pal = smoke.phase("§V pallas/sim, ExecConfig defaults", run_paper,
                      pal_spec, "sim", ExecConfig())
    dist = smoke.phase("§V pallas/dist", run_paper, pal_spec, "dist",
                       no_regret)
    ref = smoke.phase("§V reference/sim at highest precision", run_paper,
                      ref_spec, "sim", no_regret, highest=True)
    if None not in (pal, dist, ref):
        smoke.phase("compare one-chip runs", compare_one_chip, pal, dist, ref)


def on_distinct_devices(arr, count: int) -> None:
    devs = {s.device for s in arr.addressable_shards}
    check(len(devs) == count and not arr.sharding.is_fully_replicated,
          f"state is on {len(devs)} devices (replicated="
          f"{arr.sharding.is_fully_replicated}), expected {count} shards")
    print(f"       state shards on {len(devs)} distinct devices: "
          f"{sorted(d.id for d in devs)}", flush=True)


def node_sharded(backend: str, seed: int) -> None:
    import numpy as np
    from repro.api import ExecConfig
    spec = paper_spec(backend, seed)
    cfg = ExecConfig(compute_regret=False)
    highest = backend == "reference"
    one = run_paper(spec, "sim", cfg, highest=highest)
    four = run_paper(spec, "sim", cfg.replace(node_devices=4),
                     highest=highest)
    on_distinct_devices(four.final_state.theta, 4)
    check(np.array_equal(one.eps_ledger, four.eps_ledger),
          "eps_ledger differs between 1 and 4 chips")
    deviations(one, four, f"{backend} 4 chips vs 1")


def seed_sharded(seed: int) -> None:
    from repro.api import ExecConfig
    from repro.api.runner import run_batch
    from repro.launch.mesh import seed_mesh
    spec = paper_spec("pallas", seed, horizon=512)
    seeds = [seed + i for i in range(4)]
    cfg = ExecConfig(compute_regret=False, chunk_rounds=128)
    mesh = seed_mesh(4)
    check(len(set(mesh.devices.flat)) == 4, "seed mesh is not 4 devices")
    vm = run_batch(spec, seeds, exec=cfg)
    sh = run_batch(spec, seeds, exec=cfg.replace(mesh=mesh))
    check(sh[0].metrics["batch"]["devices"] == 4,
          f"run_batch used {sh[0].metrics['batch']['devices']} devices")
    for s, a, b in zip(seeds, vm, sh):
        check(identical(a, b), f"seed {s}: sharded != vmap")
    print(f"       run_batch seeds {seeds} (§V widths, 512 rounds): "
          f"4-chip seed sharding bit-identical to the one-device vmap; "
          f"{sh[0].rounds_per_sec:.1f} rounds/s (smoke reading)", flush=True)


def four_chips(smoke: Smoke, seed: int) -> None:
    for backend in ("pallas", "reference"):
        smoke.phase(f"§V node-sharded over 4 chips vs 1 chip ({backend})",
                    node_sharded, backend, seed)
    smoke.phase("run_batch seed-sharded over 4 chips vs vmap", seed_sharded,
                seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    info = device_info(args.chips)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    hits = {"/jax/compilation_cache/cache_hits": 0,
            "/jax/compilation_cache/cache_misses": 0}

    def count(event, **_):
        if event in hits:
            hits[event] += 1
    jax.monitoring.register_event_listener(count)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({entries} entries at start)",
          flush=True)

    smoke = Smoke()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(smoke, args.seed)
    else:
        one_chip(smoke, args.seed)
    print(f"compile cache: {hits['/jax/compilation_cache/cache_hits']} hits, "
          f"{hits['/jax/compilation_cache/cache_misses']} written; "
          f"total {time.perf_counter() - t0:.1f}s", flush=True)
    if smoke.failed:
        print(f"FAILED phases: {smoke.failed}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
