"""The trace reduction and the per-layer readers, on a small trace recorded on
a TPU v5e: the §V learner at m = 64, n = 1,024, 16-round chunks each made by
a program of its own just before it runs, three chunks inside the window
annotation."""
from pathlib import Path

import pytest

from chipbench import harness, peaks, roofline, trace

DATA = Path(__file__).parent / "data" / "small_stream.xplane.pb"
CHUNK = 16

STATS_TEXT = (
    "%round_stats.9 = (f32[64,128]{1,0:T(8,128)S(1)}, "
    "f32[64,128]{1,0:T(8,128)S(1)}, f32[64,128]{1,0:T(8,128)S(1)}, "
    "f32[64,128]{1,0:T(8,128)S(1)}, f32[8,10112]{1,0:T(8,128)}) "
    "custom-call(f32[64,10112]{1,0:T(8,128)S(1)} %pad.99, "
    "f32[64,10112]{1,0:T(8,128)S(1)} %pad.101, "
    "f32[1,4]{1,0:T(1,128)S(1)} %fusion.31), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    "{f32[64,10112]{1,0}, f32[64,10112]{1,0}, f32[1,4]{1,0}}")


@pytest.fixture(scope="module")
def red():
    return trace.reduce_trace(str(DATA))


@pytest.fixture(scope="module")
def cell(red):
    return {"m": 64, "n": 1024, "chips": 1, "chunk_rounds": CHUNK,
            "peaks": peaks.peaks("TPU v5 lite"),
            "rounds": trace.rounds_traced(red, CHUNK)}


def test_intervals():
    assert trace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace.intersect([(0, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                          (6, 10)]
    assert trace.length([(0, 2), (3, 5)]) == 4


def test_hlo_text():
    name, base, opcode, kind = trace.classify(STATS_TEXT)
    assert (name, base, opcode, kind) == ("round_stats.9", "round_stats",
                                          "custom-call", "kernel")
    _, shape, _, rest = trace.split_instruction(STATS_TEXT)
    results = trace.shape_bytes(shape)
    operands = trace.shape_bytes(trace.operand_text(rest))
    # four (64, 128) reductions in VMEM, the (8, 10112) column sums in HBM
    assert results == {1: 4 * 64 * 128 * 4, 0: 8 * 10112 * 4}
    assert operands == {1: 2 * 64 * 10112 * 4 + 4 * 4}
    assert trace.classify("%while.5 = (s32[], f32[4]) while((s32[], f32[4]) "
                          "%t), condition=%c, body=%b")[3] == "container"
    assert trace.classify("%collective-permute-done.2 = f32[8,128]{1,0} "
                          "collective-permute-done(f32[8,128]{1,0} %s)"
                          )[3] == "collective"
    assert trace.classify("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), "
                          "kind=kLoop, calls=%f")[3] == "xla"


def test_window_and_programs(red):
    assert len(red.devices) == 1
    assert 0 < red.window_ns < 1e9
    dev = red.devices[0]
    # three chunk programs, each preceded by its stream program
    assert len(trace.chunk_spans(dev)) == 3
    assert trace.rounds_traced(red, CHUNK) == 3 * CHUNK
    assert {name for name, _, _ in dev.modules} >= {trace.CHUNK_PROGRAM,
                                                    "jit_make"}
    for op in trace.chunk_ops(dev):
        assert any(a <= op.start and op.end <= b
                   for a, b in trace.chunk_spans(dev))


def test_busy_and_idle_add_up(red):
    busy = trace.busy_seconds(red)
    assert 0 < busy < red.window_ns / 1e9
    idle = sum(trace.idle_by_host(red).values())
    assert idle + busy == pytest.approx(red.window_ns / 1e9, rel=1e-9)
    bd = trace.breakdown(red)
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert {"round_update", "round_stats"} <= {n for n, _ in bd["device_ops"]}


def test_readers(red, cell):
    value = {m: harness.reader(m)(red, cell) for m in (
        "device_idle_share", "chunk_gap_us", "round_xla_us", "round_stats_roofline", "round_update_roofline",
        "halo_exposed_us", "round_mfu")}
    assert 0 < value["device_idle_share"] < 100
    assert value["device_idle_share"] == pytest.approx(
        100 * (1 - trace.busy_seconds(red) * 1e9 / red.window_ns))
    assert value["chunk_gap_us"] > 0
    assert value["round_xla_us"] > 0
    assert value["halo_exposed_us"] is None      # one chip, no collective
    for share in ("round_stats_roofline", "round_update_roofline",
                  "round_mfu"):
        assert 0 < value[share] <= 100


def test_roofline_by_hand(red, cell):
    """The round_update share recomputed from one call by hand."""
    calls = [op for op in trace.chunk_ops(red.devices[0], ("kernel",))
             if op.base == "round_update"]
    assert len(calls) == 3 * CHUNK
    op = calls[0]
    reads, writes = roofline.call_bytes(op)
    shapes = roofline.operand_shapes(op)
    m_pad, n_pad = shapes[1]
    assert (m_pad, n_pad) == (64, 1024)
    p = cell["peaks"]
    hbm = reads.get(0, 0) + writes.get(0, 0)
    vmem = reads.get(1, 0) / p["vmem_read_bytes"] \
        + writes.get(1, 0) / p["vmem_write_bytes"]
    flops = 2 * 64 * 64 * 1024 + 7 * 64 * 1024
    least = max(hbm / p["hbm_bytes"], vmem, flops / p["flops"])
    assert roofline.least_seconds(op, p, flops)[0] == pytest.approx(least)
    total = sum(roofline.least_seconds(c, p, flops)[0] for c in calls)
    spent = sum(c.dur for c in calls) / 1e9
    assert harness.reader("round_update_roofline")(red, cell) == \
        pytest.approx(100 * total / spent)


def test_stream_reader(red, cell):
    """stream_us_per_round reads the generator's program executions: the
    recorded trace has no ``jit_stream_chunk``, and each of its 16-round
    chunks is made by ``jit_make``."""
    from chipbench.metrics import stream_us_per_round as stream

    assert harness.reader("stream_us_per_round")(red, cell) is None
    made = [(a, b) for name, a, b in red.devices[0].modules
            if name == "jit_make"]
    assert len(made) >= 3
    by_hand = sum(b - a for a, b in made) / len(made) / CHUNK / 1e3
    value = stream.per_round(red, "jit_make", CHUNK)
    assert 0 < value == pytest.approx(by_hand)


def test_timed_program_is_the_drivers(red):
    """The reduction counts the program it is given as the timed one."""
    other = trace.reduce_trace(str(DATA), program="jit_make")
    assert [d.program for d in other.devices] == ["jit_make"]
    made = trace.union((a, b) for name, a, b in red.devices[0].modules
                       if name == "jit_make")
    assert trace.chunk_spans(other.devices[0]) == made
    assert trace.chunk_spans(red.devices[0]) != made
