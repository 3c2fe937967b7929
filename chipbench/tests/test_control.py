"""The control: the reference one precision below the configuration's,
put in the program's place, comes out not correct under the cell's limits.

sec5's control (three bf16 passes per contraction) needs the cell's own
widths and a few hundred rounds to move past float32 rounding: at m = 64,
n = 10,000 and 3 chunks of 128 rounds it reads loss_gap 5.6e-5, sparsity_gap
7 and w_gap 1.6e-4 on the CPU, against limits 5e-5, 6 and 1e-4 (on the chip,
over 1,536 rounds: 0.041, 27 and 0.091). ring64k's bfloat16 control fails
at any size."""
import pytest

from chipbench import compare, control
from chipbench.drivers import gossip_linear as linear
from chipbench.tests import cells

CASES = {
    "sec5.replay": lambda: cells.tiny_cell("sec5.replay", {},
                                           {"chunk_rounds": 128}),
    "sec5.stream": lambda: cells.tiny_cell("sec5.stream", {},
                                           {"chunk_rounds": 128}),
    "ring64k.sharded4": cells.ring64k_tiny,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_control_is_not_correct(name):
    cell = CASES[name]()
    cfg = cell["config"]
    _, _, traffic, sharding = linear.build(cell, cells.SEED)
    traffic.prepare()
    low = control.reference_outputs(cell, cells.SEED, traffic, sharding,
                                    precision=cfg["control"])
    ref = control.reference_outputs(cell, cells.SEED, traffic, sharding,
                                    follow=low["loss"])
    nodes, dim, _ = linear.sizes(cfg)
    entries = nodes * dim
    alone = control.reference_outputs(cell, cells.SEED, traffic, sharding)
    same, _ = compare.judge(compare.readings(alone, alone, entries=entries),
                            cfg["limits"])
    assert same
    ok, checks = compare.judge(compare.readings(low, ref, entries=entries),
                               cfg["limits"])
    assert not ok, checks


@pytest.mark.parametrize("change", [
    {"faults": "dcn"}, {"mixer": "complete"}, {"calibration": "global"},
    {"mechanism": "gaussian"}, {"delay": 2},
    {"mixer_options": {"self_weight": 0.5, "lazy": True}}],
    ids=lambda c: next(iter(c)))
def test_reference_refuses_what_it_does_not_cover(change):
    """A configuration whose spec sets what the reference does not compute
    is refused, not compared against the wrong learner."""
    from chipbench.references import gossip_omd

    cfg = cells.sec5_tiny()["config"]
    cfg["spec"].update(change)
    with pytest.raises(ValueError):
        gossip_omd.Reference(cfg)


def test_reference_follows_the_compared_side():
    """Following a run's own losses reproduces it bit for bit; a loss moved
    to the other side of the hinge is followed, and parts the learners."""
    import numpy as np

    cell = cells.ring64k_tiny()
    _, _, traffic, sharding = linear.build(cell, cells.SEED)
    traffic.prepare()
    alone = control.reference_outputs(cell, cells.SEED, traffic, sharding)
    same = control.reference_outputs(cell, cells.SEED, traffic, sharding,
                                     follow=alone["loss"])
    for key in ("loss", "correct", "w_bar_loss", "sparsity"):
        assert np.array_equal(alone[key], same[key]), key
    assert np.array_equal(np.asarray(alone["w"]), np.asarray(same["w"]))
    flipped = alone["loss"].copy()
    row = 1                                   # round 2: w is no longer 0
    node = int(np.argmax(flipped[row] > 0))
    flipped[row, node] = 0.0                  # the other side of the kink
    moved = control.reference_outputs(cell, cells.SEED, traffic, sharding,
                                      follow=flipped)
    assert not np.array_equal(np.asarray(alone["w"]), np.asarray(moved["w"]))
