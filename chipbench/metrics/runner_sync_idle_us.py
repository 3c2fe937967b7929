"""runner_sync_idle_us (us per chunk): the mean, over the gaps between
consecutive chunk programs and over the chips, of the device-idle time of a
gap during which the host sits in ``run.wait`` (`jax.block_until_ready`)
and in none of the runner's host phases (`runner_host_us`), with the host's
spans on the chip's clock (`runner_host_us.on_chip`): the wake-up after a
program ends, and the launch latency before the next one starts beyond its
median (which that alignment takes as 0). Layer: the runner's chunk loop.
Moves samples_per_s. Returns nothing where the trace has no ``run.wait``
span, no launch or no gap."""
from chipbench import trace
from chipbench.metrics import runner_host_us as phases


def read(r: trace.Reduction, cell: dict) -> float | None:
    sync, count = 0.0, 0
    for dev, host in phases.on_chip(r):
        wait = phases.spans(host, (phases.WAIT,))
        if not wait:
            return None
        work = phases.spans(host, phases.HOST_PHASES)
        for idle in phases.gaps(dev):
            sync += trace.length(trace.subtract(trace.intersect(idle, wait),
                                                work))
            count += 1
    if not count:
        return None
    return sync / count / 1e3
