import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes, WITHOUT allocating real tensors (ShapeDtypeStruct only).

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--strategy gossip]

Per run it prints/records:
  * compiled.memory_analysis()  — bytes per device (proves it fits)
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline
  * collective-op operand bytes parsed from the HLO (§Roofline third term)
Results land in experiments/dryrun/<arch>__<shape>__<mesh>__<strategy>.json.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch import hlo_analysis, hlo_cost, steps
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, gossip_nodes, gossip_axes
from repro.models import build_model
from repro.models.config import INPUT_SHAPES
from repro.sharding import rules as shard_rules

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


_cost_get = hlo_cost.cost_analysis_get


def count_params(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def active_param_count(cfg, params_struct) -> float:
    """N_active for MODEL_FLOPS = 6 N D: MoE counts only routed-active experts."""
    total = count_params(params_struct)
    if cfg.num_experts:
        # expert stacks: gate/up/down (E, ..) — count k/E of them (+ shared fully)
        expert = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(params_struct)[0]:
            pstr = "/".join(str(getattr(p, "key", p)) for p in path)
            if "moe/" in pstr and ("gate" in pstr or "up" in pstr or "down" in pstr):
                expert += int(np.prod(leaf.shape))
        active = total - expert + expert * cfg.num_experts_per_tok / cfg.num_experts
        return active
    return total


def pick_microbatches(cfg, shape, mesh) -> int:
    """Grad-accumulation factor so the per-chip remat carry stack (layers x
    per-node-microbatch x seq x d_model x 2B) stays under ~2 GB."""
    from repro.launch.mesh import gossip_nodes
    nodes = gossip_nodes(mesh)
    pnb = max(shape.global_batch // nodes, 1)
    if "pod" in mesh.axis_names:
        pnb = max(pnb // mesh.shape["data"], 1)
    layers_total = cfg.num_layers + cfg.encoder_layers
    carry = layers_total * pnb * shape.seq_len * cfg.d_model * 2
    m = 1
    while carry / m > 2e9 and m < pnb:
        m *= 2
    return m


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               strategy: str = "gossip", recipe: steps.TrainRecipe | None = None,
               save: bool = True, verbose: bool = True, opt: str = "",
               delay: int = 0, delay_dist: str | None = None) -> dict:
    """opt: comma-separated perf-variant flags ('last_only', ...) — results
    are saved under strategy+opt so baselines stay untouched. delay /
    delay_dist install a history ring for WAN-stale gossip (ignored when an
    explicit recipe is passed)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    opt_flags = set(f for f in opt.split(",") if f)
    strategy_tag = strategy + ("+" + opt if opt else "")
    shape = INPUT_SHAPES[shape_name]
    base_cfg = get_config(arch)
    reason = steps.skip_reason(base_cfg, shape)
    if reason:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "strategy": strategy, "status": "skipped", "reason": reason}
        if save:
            _save(rec)
        if verbose:
            print(f"[skip] {arch} x {shape_name}: {reason}")
        return rec

    if "bigq" in opt_flags:
        from repro.models import attention as _attn
        _attn.Q_CHUNK = 1024  # §Perf H3 iter 3: halve k/v reload count
    cfg = steps.effective_config(base_cfg, shape)
    model = build_model(cfg)
    if recipe is None:
        recipe = steps.TrainRecipe(
            strategy=strategy,
            microbatches=pick_microbatches(cfg, shape, mesh) if shape.kind == "train" else 1,
            delay=delay, delay_dist=delay_dist,
        )
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    with mesh:
        if shape.kind == "train":
            if strategy == "gossip":
                nodes = gossip_nodes(mesh)
                gdp = steps.make_gossip_dp(nodes, recipe)
                step = steps.make_gossip_train_step(
                    model, gdp, recipe.microbatches,
                    node_axis=gossip_axes(mesh)[0] if "batchpar" in opt_flags else None,
                    batchpar_attn="batchpar" in opt_flags,
                    head_pad=16 if "padheads" in opt_flags else None,
                    flash="flash" in opt_flags)
                init = steps.make_gossip_init(model, gdp, nodes)
                state_struct = jax.eval_shape(init)
                node_axes = gossip_axes(mesh)
                theta_specs = shard_rules.param_pspecs(
                    state_struct.gossip.theta, node_axes=node_axes, mesh=mesh)
                if "zerotheta" in opt_flags and multi_pod:
                    # Beyond-paper: ZeRO-shard theta over the intra-pod
                    # "data" axis (each pod = one gossip node owns its theta,
                    # but stores it sharded across its 256 chips). Gossip
                    # ppermutes over "pod" work on sharded leaves unchanged.
                    from jax.sharding import PartitionSpec as P
                    def _zero(path, spec_leaf):
                        leaf = None
                        # find matching struct leaf for divisibility check
                        import jax.tree_util as jtu
                        return spec_leaf
                    def _add_data(spec, leaf):
                        dims = list(spec) + [None] * (leaf.ndim - len(spec))
                        if "data" in dims:
                            return spec
                        for i in range(1, leaf.ndim):
                            if dims[i] is None and leaf.shape[i] % mesh.shape["data"] == 0                                     and leaf.shape[i] >= mesh.shape["data"]:
                                dims[i] = "data"
                                return P(*dims)
                        return spec
                    theta_specs = jax.tree_util.tree_map(
                        _add_data, theta_specs, state_struct.gossip.theta,
                        is_leaf=lambda x: isinstance(x, P))
                state_specs = steps.gossip_state_pspecs(state_struct,
                                                        theta_specs)
            else:
                step, init = steps.make_allreduce_train_step(model, recipe)
                state_struct = jax.eval_shape(init)
                from jax.sharding import PartitionSpec as P
                pspecs = shard_rules.param_pspecs(state_struct.params, mesh=mesh)
                opt_specs = {
                    "step": P(),
                    "m": shard_rules.param_pspecs(state_struct.opt["m"], mesh=mesh),
                    "v": shard_rules.param_pspecs(state_struct.opt["v"], mesh=mesh),
                }
                state_specs = steps.AllreduceTrainState(params=pspecs, opt=opt_specs)
            if "ep" in opt_flags:
                # Beyond-paper: EXPERT-PARALLEL MoE — shard the expert axis
                # over "model" (llama4: 16 experts / 16 chips). Expert
                # buffers shrink 16x; dispatch becomes a token all-to-all.
                import re as _re
                from jax.sharding import PartitionSpec as P
                def _ep(path, spec_leaf):
                    ps = "/".join(str(getattr(q, "key", q)) for q in path)
                    if _re.search(r"moe/(gate|up|down)$", ps):
                        nd = 4 if strategy == "gossip" else 3  # node axis?
                        lead = list(spec_leaf)[:1] if strategy == "gossip" else []
                        return P(*(lead + ["model", None, None]))
                    return spec_leaf
                if strategy == "gossip":
                    theta_specs = jax.tree_util.tree_map_with_path(
                        _ep, theta_specs, is_leaf=lambda x: isinstance(x, P))
                    state_specs = steps.gossip_state_pspecs(state_struct,
                                                            theta_specs)
            batch_struct, batch_specs = steps.train_batch_specs(cfg, shape, mesh, strategy)
            in_shardings = (steps.named(mesh, state_specs), steps.named(mesh, batch_specs))
            fn = jax.jit(step, in_shardings=in_shardings, donate_argnums=(0,))
            lowered = fn.lower(state_struct, batch_struct)
        elif shape.kind == "prefill":
            params_struct = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            pspecs = shard_rules.param_pspecs(params_struct, mesh=mesh)
            if "repattn" in opt_flags:
                # H1 iter 4: replicate attention weights over the model axis
                # so the T-sharded attention region has one consistent layout
                import re as _re
                from jax.sharding import PartitionSpec as P
                def _rep(path, spec):
                    ps = "/".join(str(getattr(q, "key", q)) for q in path)
                    if _re.search(r"(attn|cross)/w[qkvo]", ps):
                        return P()
                    return spec
                pspecs = jax.tree_util.tree_map_with_path(_rep, pspecs,
                    is_leaf=lambda x: isinstance(x, P))
            batch_struct, batch_specs = steps.train_batch_specs(
                cfg, shape, mesh, "allreduce")
            batch_struct.pop("labels"); batch_specs.pop("labels")
            fn = jax.jit(steps.make_prefill_step(model, last_only="last_only" in opt_flags,
                                                 seqpar_axis="model" if "seqpar" in opt_flags else None,
                                                 moe_groups=16 if "moegroup" in opt_flags else 1,
                                                 moe_group_axis="data" if "moegroup" in opt_flags else None,
                                                 head_pad=16 if "padheads" in opt_flags else None,
                                                 sp_axis="model" if "spres" in opt_flags else None),
                         in_shardings=(steps.named(mesh, pspecs),
                                       steps.named(mesh, batch_specs)))
            lowered = fn.lower(params_struct, batch_struct)
        else:  # decode
            params_struct = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            pspecs = shard_rules.param_pspecs(params_struct, mesh=mesh)
            cache_len = steps.decode_cache_len(cfg, shape)
            cache_struct = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, cache_len))
            baxes = steps.batch_axes_for_serve(mesh, shape.global_batch)
            cache_specs = shard_rules.cache_pspecs(cache_struct, baxes or ("data",), mesh=mesh)
            if not baxes:  # batch too small to shard: replicate batch dims
                from jax.sharding import PartitionSpec as P
                cache_specs = jax.tree_util.tree_map(
                    lambda s: P(*[None if d in ("data", "pod") or
                                  (isinstance(d, tuple)) else d for d in s]),
                    cache_specs, is_leaf=lambda x: isinstance(x, P))
            (tok_struct, pos_struct), (tok_spec, pos_spec) = steps.serve_batch_specs(
                cfg, shape, mesh)
            fn = jax.jit(steps.make_serve_step(model),
                         in_shardings=(steps.named(mesh, pspecs),
                                       steps.named(mesh, cache_specs),
                                       steps.named(mesh, tok_spec),
                                       steps.named(mesh, pos_spec)),
                         donate_argnums=(1,))
            lowered = fn.lower(params_struct, cache_struct, tok_struct, pos_struct)

        compiled = lowered.compile()

    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()

    # loop-aware roll-up cost model (per-device; see hlo_cost.py for why
    # raw cost_analysis undercounts scanned layers on the CPU backend)
    rollup = hlo_cost.analyze(hlo)
    flops = rollup.flops
    hbm_bytes = rollup.hbm_bytes
    coll_bytes = rollup.collective_bytes
    terms = hlo_analysis.roofline_terms(flops, hbm_bytes, coll_bytes, chips=1)

    # MODEL_FLOPS = 6 N D (training: fwd+bwd is already in the 6ND rule;
    # decode: D = global_batch tokens)
    params_struct = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    n_active = active_param_count(cfg, params_struct)
    n_total = count_params(params_struct)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = hlo_analysis.model_flops(n_active, tokens)
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = hlo_analysis.model_flops(n_active, tokens) / 3.0  # fwd only: 2ND
    else:
        tokens = shape.global_batch
        mf = hlo_analysis.model_flops(n_active, tokens) / 3.0

    bytes_per_device = None
    if mem is not None:
        try:
            bytes_per_device = {
                "temp": int(getattr(mem, "temp_size_in_bytes", 0)),
                "arguments": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output": int(getattr(mem, "output_size_in_bytes", 0)),
                "alias": int(getattr(mem, "alias_size_in_bytes", 0)),
            }
        except Exception:
            bytes_per_device = {"repr": str(mem)}

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "strategy": strategy_tag,
        "status": "ok", "chips": chips, "compile_s": round(compile_s, 1),
        "hlo_flops": flops, "hlo_bytes": hbm_bytes,
        "collectives": rollup.summary(),
        "xla_cost_analysis_raw": {"flops": _cost_get(cost, "flops"),
                                  "bytes_accessed": _cost_get(cost, "bytes accessed")},
        "roofline": terms,
        "model_flops_6nd": mf,
        "useful_flops_ratio": (mf / (flops * chips)) if flops else None,
        "n_params": n_total, "n_params_active": n_active,
        "memory_per_device": bytes_per_device,
    }
    if save:
        _save(rec)
    if verbose:
        print(f"[ok] {arch} x {shape_name} @ {mesh_name}/{strategy}: "
              f"compile {compile_s:.0f}s flops={flops:.3g} bytes={hbm_bytes:.3g} "
              f"coll={coll_bytes:.3g}B dominant={terms['dominant']} "
              f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'], 3)}")
    return rec


def dryrun_stream(stream: str, *, engine: str = "sim", nodes: int = 8,
                  dim: int = 256, chunk_rounds: int = 64,
                  stream_options: dict | None = None, save: bool = True,
                  verbose: bool = True) -> dict:
    """Lower + compile the exact chunk program `repro.api.run` scans for a
    STREAMS scenario (no real horizon executed) and record its HLO cost —
    proves a declared scenario compiles on either engine before you pay for
    the run."""
    from repro.api import RunSpec
    from repro.api.runner import make_chunk_fn

    spec = RunSpec(nodes=nodes, dim=dim, horizon=chunk_rounds, eps=1.0,
                   alpha0=0.5, lam=0.01, stream=stream,
                   stream_options=stream_options or {})
    fn, state = make_chunk_fn(spec, engine)
    xs = jax.ShapeDtypeStruct((chunk_rounds, nodes, dim), np.float32)
    ys = jax.ShapeDtypeStruct((chunk_rounds, nodes), np.float32)
    t0 = time.time()
    compiled = jax.jit(fn).lower(state, xs, ys).compile()
    compile_s = time.time() - t0
    rollup = hlo_cost.analyze(compiled.as_text())
    rec = {
        "arch": f"stream-{stream}", "shape": f"chunk{chunk_rounds}",
        "mesh": "host", "strategy": engine, "status": "ok",
        "stream": stream, "engine": engine, "nodes": nodes, "dim": dim,
        "chunk_rounds": chunk_rounds, "compile_s": round(compile_s, 1),
        "hlo_flops": rollup.flops, "hlo_bytes": rollup.hbm_bytes,
        "collectives": rollup.summary(),
    }
    if save:
        _save(rec)
    if verbose:
        print(f"[ok] stream={stream} engine={engine} m={nodes} n={dim} "
              f"chunk={chunk_rounds}: compile {compile_s:.1f}s "
              f"flops={rollup.flops:.3g} bytes={rollup.hbm_bytes:.3g}")
    return rec


def _save(rec: dict) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    fn = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}__{rec['strategy']}.json"
    with open(os.path.join(OUT_DIR, fn), "w") as f:
        json.dump(rec, f, indent=2)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="gossip", choices=["gossip", "allreduce"])
    ap.add_argument("--opt", default="", help="perf-variant flags, comma separated")
    ap.add_argument("--delay", type=int, default=0,
                    help="WAN gossip staleness (rounds); adds the history "
                         "ring to the lowered GossipState")
    ap.add_argument("--delay-dist", default=None,
                    choices=["constant", "uniform", "geometric"])
    ap.add_argument("--stream", default=None,
                    help="repro.api STREAMS name: lower/compile the "
                         "repro.api.run chunk program instead of an arch")
    ap.add_argument("--stream-opt", action="append", default=[],
                    metavar="KEY=VALUE")
    ap.add_argument("--engine", default="sim", choices=["sim", "dist"])
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--chunk-rounds", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    if args.stream:
        from repro.launch.train import parse_stream_options
        dryrun_stream(args.stream, engine=args.engine, nodes=args.nodes,
                      dim=args.dim, chunk_rounds=args.chunk_rounds,
                      stream_options=parse_stream_options(args.stream_opt))
        return 0

    runs = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in INPUT_SHAPES:
                runs.append((arch, shape))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        runs.append((args.arch, args.shape))

    failures = 0
    for arch, shape in runs:
        try:
            dryrun_one(arch, shape, multi_pod=args.multi_pod, strategy=args.strategy,
                       opt=args.opt, delay=args.delay, delay_dist=args.delay_dist)
        except Exception:
            failures += 1
            print(f"[FAIL] {arch} x {shape}:\n{traceback.format_exc()}")
            _save({"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "strategy": args.strategy, "status": "failed",
                   "error": traceback.format_exc()[-2000:]})
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
