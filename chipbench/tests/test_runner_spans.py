"""The runner-span readers (runner_host_us, runner_exposed_us,
runner_sync_idle_us) on hand-built trace reductions, where every value can
be worked out by hand, and on a recorded trace whose runner wrote no
``run.*`` spans. Times are in us here and in ns in a reduction; the readers
give us."""
from pathlib import Path

import pytest

from chipbench import harness, trace
from chipbench.metrics import runner_host_us

DATA = Path(__file__).parent / "data" / "small_stream.xplane.pb"
READERS = ("runner_host_us", "runner_exposed_us", "runner_sync_idle_us")
PROG = trace.CHUNK_PROGRAM
LAUNCH = runner_host_us.LAUNCH


def ns(spans, shift=0.0):
    return [(a * 1e3 + shift * 1e3, b * 1e3 + shift * 1e3) for a, b in spans]


def device(name, chunks, ops, shift=0.0):
    """A chip whose plane sits ``shift`` us off the host's clock."""
    return trace.Device(
        name, [(PROG, a, b) for a, b in ns(chunks, shift)],
        [trace.Op("fusion.1", "fusion", "fusion", "xla", a, b, "")
         for a, b in ns(ops, shift)])


def dispatch(start, launch, end):
    """A ``run.dispatch`` span whose program launch returns at ``launch``."""
    return [("run.dispatch", start, end), (LAUNCH, launch - 0.5, launch)]


def reduction(window, devices, host):
    return trace.Reduction(tuple(w * 1e3 for w in window), devices,
                           sorted((n, a * 1e3, b * 1e3) for n, a, b in host))


def values(r, chunk_rounds=4):
    cell = {"rounds": trace.rounds_traced(r, chunk_rounds),
            "chunk_rounds": chunk_rounds}
    return {m: harness.reader(m)(r, cell) for m in READERS}


def one_gap(shifts=(0.0,)):
    # chunk programs 0-10 and 20-30 us on each chip, launched at 0 and 20;
    # the 10 us gap is 2 us of run.wait (wake-up), 5 of fetch and 3 of
    # dispatch up to the launch. The on_chunk before the first launch and
    # the fetch after the last lie outside the loop's whole iterations.
    chunks = [(0, 10), (20, 30)]
    devs = [device(f"/device:TPU:{i}", chunks, chunks, s)
            for i, s in enumerate(shifts)]
    host = [("run.on_chunk", -5, -3), *dispatch(-2, 0, 0.5),
            ("run.chunk", -2, 12), ("run.wait", 0.5, 12),
            ("run.fetch", 12, 17), *dispatch(17, 20, 20.5),
            ("run.chunk", 17, 32), ("run.wait", 20.5, 32),
            ("run.fetch", 32, 37)]
    return reduction((-6, 38), devs, host)


def test_gap_split_between_fetch_and_wait():
    r = one_gap()
    # host: 0.5 us of the first dispatch, 5 of fetch, 3 of the second
    # dispatch, over one iteration
    assert values(r) == {"runner_host_us": 8.5, "runner_exposed_us": 8.0,
                         "runner_sync_idle_us": 2.0}
    assert harness.reader("chunk_gap_us")(r, {}) == 10.0


def test_clock_offset_is_taken_out():
    # the same loop, with one chip's plane 7 us early on the host's clock
    # and another's 3 us late: each is put back by its own offset (on the
    # chip, offsets of up to ~1.3 ms against chunk periods of 50 ms or more)
    r = one_gap(shifts=(-7.0, 3.0))
    assert values(r) == {"runner_host_us": 8.5, "runner_exposed_us": 8.0,
                         "runner_sync_idle_us": 2.0}


def test_offset_is_the_median_launch_latency():
    # three programs that start 1, 0 and 4 us after their launches: the
    # offset is 1 us, and the spread about it stays in the gaps
    chunks = [(1, 10), (20, 30), (44, 50)]
    host = [*dispatch(-2, 0, 0.5), ("run.wait", 0.5, 12),
            ("run.fetch", 12, 17), *dispatch(17, 20, 20.5),
            ("run.wait", 20.5, 33), ("run.fetch", 33, 38),
            *dispatch(38, 40, 40.5), ("run.wait", 40.5, 51)]
    r = reduction((-3, 52), [device("/device:TPU:0", chunks, chunks)], host)
    # gaps 10-20 and 30-44 us on the chip, 9-19 and 29-43 on the host:
    # (wait 3, fetch 5, dispatch 2) and (wait 4, fetch 5, dispatch 2.5,
    # wait 2.5); host 0.5 + 5 + 3.5 + 5 + 2 us over two iterations
    assert values(r) == {"runner_host_us": 8.0, "runner_exposed_us": 7.25,
                         "runner_sync_idle_us": 4.75}


def test_host_phase_over_a_busy_op_is_not_exposed():
    # run.account opens 3 us before the first program ends and run.stream
    # runs over an op that fills 2 us of the gap: only the idle parts of
    # the gap count as exposed
    chunks = [(0, 10), (20, 30)]
    dev = device("/device:TPU:0", chunks, [(0, 10), (14, 16), (20, 30)])
    host = [*dispatch(-2, 0, 0.5), ("run.wait", 0.5, 7),
            ("run.account", 7, 12), ("run.stream", 13, 17),
            *dispatch(17, 20, 20.5), ("run.wait", 20.5, 31)]
    r = reduction((-3, 32), [dev], host)
    # host: 0.5 + 5 + 4 + 3 us; exposed: 10-12, 13-14, 16-17 and 17-20 us;
    # no wait in the gap, and 12-13 us in no span
    assert values(r) == {"runner_host_us": 12.5, "runner_exposed_us": 7.0,
                         "runner_sync_idle_us": 0.0}


def test_mean_over_chips_and_gaps():
    # one host loop, two chips whose programs end at different times: chip
    # 0 idles 10-20 us, chip 1 idles 12-20 us and again 30-40 us
    chunks0 = [(0, 10), (20, 30)]
    chunks1 = [(0, 12), (20, 30), (40, 50)]
    host = [*dispatch(-2, 0, 0.5), ("run.wait", 0.5, 14),
            ("run.fetch", 14, 18), *dispatch(18, 20, 20.5),
            ("run.wait", 20.5, 33), ("run.on_chunk", 33, 38),
            *dispatch(38, 40, 40.5), ("run.wait", 40.5, 52)]
    r = reduction((-3, 60), [device("/device:TPU:0", chunks0, chunks0),
                             device("/device:TPU:1", chunks1, chunks1)], host)
    v = values(r)
    # gaps (exposed, sync): chip 0 (6, 4); chip 1 (6, 2) and (7, 3); host
    # 0.5 + 4 + 2.5 + 5 + 2 us over two iterations
    assert v["runner_exposed_us"] == pytest.approx(19 / 3)
    assert v["runner_sync_idle_us"] == 3.0
    assert v["runner_host_us"] == 7.0


@pytest.mark.parametrize("shift", [0.0, 4.0])
def test_generator_before_the_chunk_aligns_on_the_first_program(shift):
    # a stream's loop: each iteration launches the generator (run.stream)
    # and then the chunk program, which queues behind it and starts 9 us
    # after its launch returns. Chunks 0-10, 30-40, 60-70 us and the
    # generator 20-30, 50-60 us on the chip; each 10 us gap is 2 us of
    # run.wait, 5 of fetch and 3 of run.stream up to the generator's
    # launch. The host is aligned on the generator, the program the chip
    # waited for, and not on the chunk program.
    gen = [(-10, 0), (20, 30), (50, 60)]
    chunks = [(0, 10), (30, 40), (60, 70)]
    dev = trace.Device(
        "/device:TPU:0",
        sorted([("jit_stream_chunk", a, b) for a, b in ns(gen, shift)]
               + [(PROG, a, b) for a, b in ns(chunks, shift)],
               key=lambda m: m[1]),
        [trace.Op("fusion.1", "fusion", "fusion", "xla", a, b, "")
         for a, b in ns(sorted(gen + chunks), shift)])
    host = [("run.stream", -13, -10), (LAUNCH, -10.5, -10),
            *dispatch(-9.5, -9, -8.5), ("run.wait", -8.5, 12)]
    for t in (0, 30):
        host += [("run.fetch", t + 12, t + 17), ("run.stream", t + 17, t + 20),
                 (LAUNCH, t + 19.5, t + 20), *dispatch(t + 20.5, t + 21,
                                                      t + 21.5),
                 ("run.wait", t + 21.5, t + 42)]
    r = reduction((-12, 75), [dev], host)
    # host between the first and last chunk launches (-9 and 51 us):
    # dispatch 0.5 + 1 + 0.5, fetch 5 + 5, run.stream 3 + 3, over two
    assert values(r) == {"runner_host_us": 9.0, "runner_exposed_us": 8.0,
                         "runner_sync_idle_us": 2.0}
    assert harness.reader("chunk_gap_us")(r, {}) == 10.0


def test_no_runner_spans_reads_nothing():
    chunks = [(0, 10), (20, 30)]
    r = reduction((0, 32), [device("/device:TPU:0", chunks, chunks)],
                  [("chipbench.on_chunk", 12, 13), (LAUNCH, 17, 18)])
    assert values(r) == dict.fromkeys(READERS)


def test_recorded_trace_without_runner_spans_reads_nothing():
    r = trace.reduce_trace(str(DATA))
    assert not any(name.startswith("run.") for name, _, _ in r.host)
    assert values(r, chunk_rounds=16) == dict.fromkeys(READERS)
