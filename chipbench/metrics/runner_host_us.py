"""runner_host_us (us per chunk): the runner's host work per chunk: the
length of the union of its host phase spans over the whole loop iterations
in the traced window, whether or not the chip waits for that work. An
iteration runs from one launch of the chunk program to the next, so the
value is the host phase time between the first and the last launch in the
window over the launches between them less one. The phases are the
`repro.api.run` chunk-loop spans that the host spends working: every
``run.*`` span of the loop other than ``run.chunk`` (which holds dispatch
and wait) and ``run.wait``. ``run.on_chunk`` is among them: in the
benchmark it holds the harness's own callback (window marks and state
capture, ~40 us a chunk on the chip), as it holds the serving trainer's
publication in a deployment. Layer: the runner's chunk loop. Moves
samples_per_s. Returns nothing where the trace has none of these spans (a
runner without them) or fewer than two launches.

A launch is the end of the host's ``PJRT_LoadedExecutable_Execute`` inside
a ``run.dispatch`` span: the moment the host has handed the chunk program
to the chip. `runner_exposed_us` and `runner_sync_idle_us` lay the host's
spans against each chip's idle gaps, and the profiler puts a TPU's plane on
the host's clock only to within about a millisecond (the offset differs
from trace to trace). So `on_chip` first moves the host spans onto each
chip's clock by the median, over the chip's chunk programs in the window,
of (program start - the nearest launch), which holds while the offset is
under half a chunk period: after it a program starts, in the median, when
its launch returns. The median launch latency is so taken as 0. Where it
is not (programs started 87-131 us after their launch returned in
`tests/data/small_stream.xplane.pb`), the host's phases sit that much later
in the gap than they ran, and their share of it holds as long as they stay
inside it: only the rest of ``run.dispatch`` after the launch moves from
the gap to the program. All this holds in a loop that blocks on each chunk,
as `repro.api.run`'s does, where every program waits for its launch. In a
loop that dispatches ahead, programs queue behind the one before and start
later than their launch; the offset then has to come from the programs the
chip waited for."""
import statistics

from chipbench import trace

HOST_PHASES = ("run.stream", "run.dispatch", "run.account", "run.fetch",
               "run.log", "run.checkpoint", "run.on_chunk")
WAIT = "run.wait"
DISPATCH = "run.dispatch"
LAUNCH = "PJRT_LoadedExecutable_Execute"


def spans(host: list, names) -> list:
    """Merged host spans of the given names."""
    return trace.union((a, b) for name, a, b in host if name in names)


def launches(r: trace.Reduction) -> list:
    """The end of the last program launch inside each ``run.dispatch``."""
    execs = [(a, b) for name, a, b in r.host if name == LAUNCH]
    out = []
    for a, b in sorted((a, b) for name, a, b in r.host if name == DISPATCH):
        ends = [e for s, e in execs if a <= s and e <= b]
        if ends:
            out.append(max(ends))
    return out


def on_chip(r: trace.Reduction) -> list:
    """[(chip, the host's spans moved onto its clock)] for each chip that ran
    a chunk program in the window; [] where the trace has no launch."""
    launched = launches(r)
    out = []
    for dev in r.devices:
        starts = [a for a, _ in trace.chunk_spans(dev)]
        if not launched or not starts:
            continue
        shift = statistics.median(
            s - min(launched, key=lambda t: abs(s - t)) for s in starts)
        out.append((dev, [(n, a + shift, b + shift) for n, a, b in r.host]))
    return out


def gaps(dev: trace.Device) -> list:
    """The device-idle intervals of each gap between two consecutive chunk
    programs on one chip (as `chunk_gap_us` reads them)."""
    chunks, ops = trace.chunk_spans(dev), trace.busy(dev)
    return [trace.subtract([(b, c)], ops)
            for (_, b), (c, _) in zip(chunks, chunks[1:])]


def read(r: trace.Reduction, cell: dict) -> float | None:
    host, launched = spans(r.host, HOST_PHASES), launches(r)
    if not host or len(launched) < 2:
        return None
    loop = [(launched[0], launched[-1])]
    return (trace.length(trace.intersect(host, loop))
            / (len(launched) - 1) / 1e3)
