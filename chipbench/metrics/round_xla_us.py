"""round_xla_us (us per round): device time of the chunk program's XLA ops,
per round: every op inside the chunk program's executions other than the
Pallas kernels, the collectives and the loop containers, averaged over the
chips of the cell. Layer: the round body (`api/backends.py`,
`api/mechanisms.py`, `core/`). Moves samples_per_s."""
from chipbench import trace


def read(r: trace.Reduction, cell: dict) -> float | None:
    if cell["rounds"] <= 0:
        return None
    total = sum(trace.length(trace.union((op.start, op.end)
                                         for op in trace.chunk_ops(d, ("xla",))))
                for d in r.devices)
    return total / len(r.devices) / cell["rounds"] / 1e3
