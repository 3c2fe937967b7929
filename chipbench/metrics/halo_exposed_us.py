"""halo_exposed_us (us per round): the time a chip spends in collectives of
the chunk program (the ppermute halo exchange, the metric psums) during
which no other op runs on it, per round, averaged over the chips of the
cell. Layer: collectives (`api/shard_node.py`). Moves samples_per_s.
Returns nothing where the chunk program has no collective."""
from chipbench import trace


def read(r: trace.Reduction, cell: dict) -> float | None:
    exposed, seen = 0.0, False
    for dev in r.devices:
        coll = trace.union((op.start, op.end) for op in
                           trace.chunk_ops(dev, ("collective",)))
        seen = seen or bool(coll)
        other = trace.union((op.start, op.end) for op in
                            trace.chunk_ops(dev, ("xla", "kernel")))
        exposed += trace.length(trace.subtract(coll, other))
    if not seen or cell["rounds"] <= 0:
        return None
    return exposed / len(r.devices) / cell["rounds"] / 1e3
