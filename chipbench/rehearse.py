"""Sizing rehearsal of a cell without the chip: compile its chunk program for
described TPU v5e chips and print what the compiler says about memory.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --config ring64k

For the configuration's size it compiles, with the chunk's data and state
given as shapes: the program node-sharded over a described ``v5e:2x2`` (4
chips), and the same program unsharded for one described chip. It prints
each program's ``memory_analysis()`` (bytes per chip), or the compiler's
refusal, and whether the per-round Laplace draw is sliced inside its fusion
(only the shard's block is drawn) or drawn whole and sliced afterwards. Nothing
runs; a compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def describe(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def noise_draw(hlo: str, m: int, n: int, block: int) -> dict:
    """What the compiled program does with the (m, n) noise draw: the
    largest RNG-bearing fusion's output rows."""
    full = f"f32[{m},{n}]"
    part = f"f32[{block},{n}]"
    rng = [ln for ln in hlo.splitlines()
           if re.search(r"\b(rng-bit-generator|threefry|xor|shift-right-logical)", ln)
           and "fusion" in ln.split("=")[0] + ln]
    return {"fusions_with_full_draw_shape": sum(full in ln for ln in rng),
            "fusions_with_block_shape": sum(part in ln for ln in rng)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--nodes", type=int, help="in place of the file's nodes")
    ap.add_argument("--chunk-rounds", type=int,
                    help="in place of the file's chunk_rounds")
    ap.add_argument("--hlo-out", help="write the sharded program's HLO here")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    from repro.api import RunSpec
    from repro.api.runner import make_chunk_program
    from repro.api.shard_node import make_node_chunk_fn

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / f"{args.config}.json").read_text())
    m = args.nodes or cfg["spec"]["nodes"]
    n = cfg["spec"]["dim"]
    c = args.chunk_rounds or cfg["exec"]["chunk_rounds"]
    spec = RunSpec(**dict(cfg["spec"], nodes=m), stream="social_sparse")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    out = {"config": args.config, "nodes": m, "dim": n, "chunk_rounds": c,
           "one_array_bytes": m * n * 4}

    # node-sharded over the described chips
    mesh = Mesh(np.array(topo.devices[:args.chips]), ("node",))
    fn, init_fn = make_node_chunk_fn(spec, "sim", mesh)
    template = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    rows = NamedSharding(mesh, P("node", None))
    rep = NamedSharding(mesh, P())
    state = template._replace(
        theta=jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=rows),
        t=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        key=jax.ShapeDtypeStruct(template.key.shape, template.key.dtype,
                                 sharding=rep))
    data = NamedSharding(mesh, P(None, "node"))
    xs = jax.ShapeDtypeStruct((c, m, n), jnp.float32, sharding=data)
    ys = jax.ShapeDtypeStruct((c, m), jnp.float32, sharding=data)
    try:
        compiled = jax.jit(fn).lower(state, xs, ys).compile()
        hlo = compiled.as_text()
        out["sharded"] = describe(compiled)
        out["sharded"]["noise"] = noise_draw(hlo, m, n, m // args.chips)
        out["sharded"]["collectives"] = sorted(set(re.findall(
            r"\b(collective-permute|all-reduce|all-gather|all-to-all)"
            r"(?:-start)?\(", hlo)))
        if args.hlo_out:
            Path(args.hlo_out).write_text(hlo)
    except Exception as err:                       # noqa: BLE001
        out["sharded"] = {"refused": f"{type(err).__name__}: {err}"[:2000]}

    # the same program unsharded, on one described chip
    one = SingleDeviceSharding(topo.devices[0])
    fn1, init1 = make_chunk_program(spec, "sim")
    t1 = jax.eval_shape(init1, jax.random.PRNGKey(0))
    state1 = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t1)
    xs1 = jax.ShapeDtypeStruct((c, m, n), jnp.float32, sharding=one)
    ys1 = jax.ShapeDtypeStruct((c, m), jnp.float32, sharding=one)
    try:
        out["unsharded"] = describe(jax.jit(fn1).lower(state1, xs1, ys1)
                                    .compile())
    except Exception as err:                       # noqa: BLE001
        out["unsharded"] = {"refused": f"{type(err).__name__}: {err}"[:2000]}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
