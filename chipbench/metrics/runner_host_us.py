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

A launch is the end of the host's ``PJRT_LoadedExecutable_Execute``: the
moment the host has handed a program to the chip; the chunk program's is
the last inside a ``run.dispatch`` span. `runner_exposed_us` and
`runner_sync_idle_us` lay the host's spans against each chip's idle gaps,
and the profiler puts a TPU's plane on the host's clock only to within
about a millisecond (the offset differs from trace to trace). So `on_chip`
first moves the host spans onto each chip's clock by the median, over the
loop iterations in the window, of (the start of the iteration's first
program on the chip - the nearest first launch of an iteration on the
host), which holds while the offset is under half a chunk period: after it
the program that the chip waited for starts, in the median, when its
launch returns. An iteration's first program is the earliest that starts
after the previous chunk program ended: the chunk program itself where the
loop launches nothing else, a stream's generator where that runs first (the
chunk program then queues behind it, and starts later than its launch).
The median launch latency is so taken as 0. Where it is not (programs
started 87-131 us after their launch returned in
`tests/data/small_stream.xplane.pb`), the host's phases sit that much later
in the gap than they ran, and their share of it holds as long as they stay
inside it: only the rest of the launching span after the launch moves from
the gap to the program. All this holds in a loop that blocks on each chunk,
as `repro.api.run`'s does, where each iteration's first program waits for
its launch. In a loop that dispatches ahead, an iteration's programs queue
behind the one before; the offset then has to come from programs that
start after the chip was idle."""
import math
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


def iteration_launches(r: trace.Reduction) -> list:
    """The first program launch of each loop iteration: the earliest launch
    after the previous iteration's chunk launch, up to its own."""
    ends = sorted(b for name, a, b in r.host if name == LAUNCH)
    out, prev = [], -math.inf
    for c in launches(r):
        out.append(min(e for e in ends if prev < e <= c))
        prev = c
    return out


def iteration_starts(dev: trace.Device) -> list:
    """The start of each loop iteration's first program on one chip: the
    earliest program that starts after the previous chunk program ended,
    up to its own chunk program's start."""
    starts = sorted(a for _, a, _ in dev.modules)
    out, prev = [], -math.inf
    for a, b in trace.chunk_spans(dev):
        out.append(min(s for s in starts if prev <= s <= a))
        prev = b
    return out


def on_chip(r: trace.Reduction) -> list:
    """[(chip, the host's spans moved onto its clock)] for each chip that ran
    a chunk program in the window; [] where the trace has no launch."""
    launched = iteration_launches(r)
    out = []
    for dev in r.devices:
        starts = iteration_starts(dev)
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
