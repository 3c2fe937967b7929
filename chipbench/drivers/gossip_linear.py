"""The driver of the paper's linear learner (Algorithm 1) through
`repro.api.run`, the default of a configuration that names none.

One operation is one chunk of ``chunk_rounds`` rounds, and a sample is one
node's row of one round. Set-up runs ``compare_chunks`` chunks through the
chunk program; once the window has closed the reference (the
configuration's ``reference.module``) follows them from the seed, and the
privacy ledger is compared over the whole run.
"""
from __future__ import annotations

import gc
import importlib

from chipbench import compare, trace


def sizes(cfg: dict) -> tuple[int, int, int]:
    """(nodes, dim, chunk_rounds) of a configuration."""
    return (int(cfg["spec"]["nodes"]), int(cfg["spec"]["dim"]),
            int(cfg["exec"]["chunk_rounds"]))


def build(cell: dict, seed: int):
    """(spec, exec config, traffic, reference sharding) of one run.

    The configuration's ``spec`` and ``exec`` go into `RunSpec` and
    `ExecConfig` as they are; the driver adds only the stream (the cell's
    traffic), the seed and, on more than one chip, the node mesh."""
    import jax
    from repro.api import ExecConfig, RunSpec

    from chipbench.generator import Traffic

    cfg, chips = cell["config"], cell["chips"]
    nodes, dim, chunk_rounds = sizes(cfg)
    mesh = shardings = ref_sharding = None
    if chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import node_mesh
        mesh = node_mesh(chips)
        data = NamedSharding(mesh, P(None, "node"))
        shardings = (data, data)
        ref_sharding = NamedSharding(mesh, P("node", None))
    traffic = Traffic(cell["traffic"], n=dim, nodes=nodes,
                      chunk_rounds=chunk_rounds,
                      horizon=int(cfg["spec"]["horizon"]), seed=seed,
                      shardings=shardings,
                      annotate=jax.profiler.TraceAnnotation)
    spec = RunSpec(**cfg["spec"], seed=int(seed) % 2**32, stream=traffic)
    exec_cfg = ExecConfig(**cfg["exec"], node_mesh=mesh)
    return spec, exec_cfg, traffic, ref_sharding


def reference_module(name: str):
    return importlib.import_module(f"chipbench.references.{name}")


class Driver:
    """One run of a linear-learner cell (see `chipbench.drivers`)."""

    program = trace.CHUNK_PROGRAM

    def __init__(self, cell: dict, seed: int):
        self.cell, self.seed, self.cfg = cell, seed, cell["config"]
        missing = [n for n in compare.NUMBERS if n not in self.cfg["limits"]]
        if missing:
            raise ValueError(f"the configuration's limits lack {missing}: "
                             "the linear learner is held to every number "
                             "of compare.NUMBERS")
        self.nodes, self.dim, self.chunk_rounds = sizes(self.cfg)
        self.open_at = int(self.cfg["compare_chunks"])
        self.samples_per_op = self.nodes * self.chunk_rounds
        self.info = {"m": self.nodes, "n": self.dim,
                     "chunk_rounds": self.chunk_rounds}
        self.spec, self.exec_cfg, self.traffic, self.ref_sharding = \
            build(cell, seed)
        self.res = self._kept = None

    def prepare(self) -> None:
        self.traffic.prepare()

    def keep(self, round_end, state, accountant) -> None:
        """The dual state and round count after the last compared chunk."""
        theta = state.theta if not isinstance(state.theta, dict) \
            else state.theta["w"]
        self._kept = (theta, state.t)

    def run(self, on_chunk) -> None:
        from repro.api import run

        self.res = run(self.spec, engine="sim", exec=self.exec_cfg,
                       on_chunk=on_chunk)

    def readings(self, ops: int) -> dict:
        import jax

        cfg, K = self.cfg, self.open_at
        theta, t = self._kept
        rule, omd = self.spec.resolve_local_rule(), self.spec.omd_config()
        w_prog = rule.primal(theta, omd.step_context(t))
        R = K * self.chunk_rounds
        res = self.res
        prog = {"loss": res.loss[:R], "correct": res.correct[:R],
                "w_bar_loss": res.w_bar_loss[:R], "sparsity": res.sparsity[:R],
                "eps": res.eps_ledger, "w": w_prog}
        # free the program's state, then the reference follows the chunks
        res.final_state = None
        del theta
        self._kept = None
        gc.collect()
        refmod = reference_module(cfg["reference"]["module"])
        ref = refmod.Reference(cfg, precision="highest",
                               sharding=self.ref_sharding)
        with jax.default_matmul_precision("highest"):
            out = ref.run(self.seed,
                          [self.traffic.chunk_data(k) for k in range(K)],
                          follow=prog["loss"])
        out["eps"] = refmod.eps_ledger(cfg["spec"]["eps"],
                                       ops * self.chunk_rounds,
                                       self.traffic.disjoint)
        return compare.readings(prog, out, entries=self.nodes * self.dim)

    def release(self) -> None:
        self.traffic.release()
