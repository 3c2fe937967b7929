"""runner_exposed_us (us per chunk): the part of the runner's host work that
the chip waits for: the mean, over the gaps between consecutive chunk
programs and over the chips, of the device-idle time of a gap during which
one of the runner's host phases (`runner_host_us`) is open, with the host's
spans on the chip's clock (`runner_host_us.on_chip`). Layer: the runner's
chunk loop. Moves samples_per_s. Returns nothing where the trace has no such
spans, no launch or no gap."""
from chipbench import trace
from chipbench.metrics import runner_host_us as phases


def read(r: trace.Reduction, cell: dict) -> float | None:
    exposed, count = 0.0, 0
    for dev, host in phases.on_chip(r):
        work = phases.spans(host, phases.HOST_PHASES)
        if not work:
            return None
        for idle in phases.gaps(dev):
            exposed += trace.length(trace.intersect(idle, work))
            count += 1
    if not count:
        return None
    return exposed / count / 1e3
