"""chunk_gap_us (us per chunk): the mean device-idle time between the end of
one execution of the chunk program and the start of the next, over the
chips of the cell. Time in that gap during which another program runs (a
stream's chunk generation) is not idle and is not counted. Layer: the
runner's chunk loop (`repro.api.run`). Moves samples_per_s."""
from chipbench import trace


def read(r: trace.Reduction, cell: dict) -> float | None:
    gaps = []
    for dev in r.devices:
        spans = trace.chunk_spans(dev)
        ops = trace.busy(dev)
        for (_, b), (c, _) in zip(spans, spans[1:]):
            idle = trace.subtract([(b, c)], ops)
            gaps.append(trace.length(idle))
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e3
