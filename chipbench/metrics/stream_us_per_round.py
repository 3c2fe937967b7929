"""stream_us_per_round (us per round): device time of the traffic
generator's program (`chipbench.generator.STREAM_PROGRAM`, one execution
per chunk of ``chunk_rounds`` rounds), per round it makes, averaged over
the chips of the cell. Layer: stream ingest, as the benchmark stands it in.
A deployment ingests rows from off the chip (`repro.api.streams`); the
benchmark makes them on the chip with its own generator, so that each
chunk is new and lands while the learner runs. The generator is the
benchmark's and no program change speeds it: only a runner that overlaps
it with the round or the host's gap (ROADMAP 1.2, 1.3) moves samples_per_s
through this layer. Returns nothing where the window holds no execution of
that program (a replayed pool)."""
from chipbench import generator, trace


def per_round(r: trace.Reduction, program: str,
              chunk_rounds: int) -> float | None:
    """Device us per round of ``program``, one execution per chunk."""
    spent, runs = 0.0, 0
    for dev in r.devices:
        spans = trace.union((a, b) for prog, a, b in dev.modules
                            if prog == program)
        spent += trace.length(spans)
        runs += len(spans)
    if not runs:
        return None
    return spent / runs / chunk_rounds / 1e3


def read(r: trace.Reduction, cell: dict) -> float | None:
    return per_round(r, generator.STREAM_PROGRAM, cell["chunk_rounds"])
