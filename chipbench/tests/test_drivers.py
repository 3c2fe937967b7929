"""Drivers found by name, the judge of the configured limits, and the
stream traffic that the ``sec5.stream`` cell runs."""
import json
import math
import textwrap

import numpy as np
import pytest

from chipbench import compare, generator, harness
from chipbench.drivers import gossip_linear as linear
from chipbench.references import gossip_omd
from chipbench.tests import cells

STUB = textwrap.dedent('''
    """A stub system: one jitted step adds 1 to a vector."""
    import jax
    import jax.numpy as jnp


    def step(x):
        return x + 1.0


    class Driver:
        program = "jit_step"

        def __init__(self, cell, seed):
            self.n = int(cell["config"]["n"])
            self.open_at = 2
            self.samples_per_op = self.n
            self.info = {"m": 1, "n": self.n, "chunk_rounds": 1}
            self.seed = seed

        def prepare(self):
            self.x0 = jnp.full((self.n,), float(self.seed % 7))

        def keep(self, x):
            self.kept = x

        def run(self, hook):
            fn, x = jax.jit(step), self.x0
            while True:
                x = jax.block_until_ready(fn(x))
                if hook(x):
                    return

        def readings(self, ops):
            ref = self.x0 + self.open_at
            return {"gap": float(jnp.max(jnp.abs(self.kept - ref)))}

        def release(self):
            self.x0 = None
''')


def bench_root(tmp_path, config: dict):
    """A checkout that holds only the harness's own files and one cell,
    ``toy.steps``, of a configuration with its own driver file."""
    (tmp_path / "chipbench" / "drivers").mkdir(parents=True)
    (tmp_path / "chipbench" / "configs").mkdir()
    (tmp_path / "chipbench" / "traffic").mkdir()
    (tmp_path / "chipbench" / "drivers" / "stub.py").write_text(STUB)
    (tmp_path / "chipbench" / "configs" / "toy.json").write_text(
        json.dumps(config))
    (tmp_path / "chipbench" / "traffic" / "steps.json").write_text("{}")
    bench = {"workloads": [{"name": "toy.steps", "config": "toy",
                            "traffic": "steps", "chips": 1}],
             "end_to_end": [{"name": "samples_per_s", "unit": "samples/s"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


@pytest.mark.parametrize("limits,correct", [
    ({"gap": 0}, True),
    ({"gap": 0, "loss_gap": 1.0}, False)], ids=["sound", "no_reading"])
def test_new_driver_is_new_files(tmp_path, limits, correct):
    """A configuration that names a driver of its own runs through the
    harness as it is; a limit whose number the driver does not read is inf
    and fails."""
    root = bench_root(tmp_path, {"driver": "stub", "n": 8, "limits": limits})
    cell = harness.load_cell("toy.steps", root=root)
    out = cells.run(cell, seconds=0.1)
    assert out["correct"] is correct, out["checks"]
    assert out["checks"]["gap"]["value"] == 0.0
    if not correct:
        assert out["checks"]["loss_gap"]["value"] == math.inf
    assert out["metrics"]["samples_per_s"]["value"] > 0
    assert out["attempted"] >= 1


def test_unknown_driver_is_refused(tmp_path):
    root = bench_root(tmp_path, {"driver": "no_such_driver", "limits": {}})
    with pytest.raises(ValueError, match="no_such_driver"):
        harness.load_cell("toy.steps", root=root)


def test_default_driver_is_the_linear_learner():
    cell = harness.load_cell("sec5.replay")
    assert "driver" not in cell["config"]
    assert cell["driver"].endswith("drivers/gossip_linear.py")
    assert harness.driver(cell).program == "jit_chunk_fn"


def test_linear_driver_refuses_missing_limits():
    """A linear-learner configuration whose limits leave out one of the six
    numbers is refused, by that number's name, before anything runs."""
    cell = cells.sec5_tiny()
    del cell["config"]["limits"]["w_gap"]
    with pytest.raises(ValueError, match="w_gap"):
        linear.Driver(cell, cells.SEED)


def test_judge_reads_the_configured_names():
    ok, checks = compare.judge({"a": 1.0, "b": 2.0}, {"a": 1})
    assert ok and list(checks) == ["a"]
    ok, checks = compare.judge({"a": 1.0}, {"a": 1, "b": 0})
    assert not ok and checks["b"] == {"value": math.inf, "limit": 0.0}


def _traffic(mode: str, seed: int = cells.SEED) -> generator.Traffic:
    mix = dict(harness.load_cell(f"sec5.{mode}")["traffic"])
    return generator.Traffic(mix, n=384, nodes=16, chunk_rounds=8,
                             horizon=1 << 20, seed=seed)


def test_stream_chunks_are_the_pool_chunks():
    """Stream chunk k is replay pool chunk k, bit for bit, for k < 3."""
    replay, stream = _traffic("replay"), _traffic("stream")
    replay.prepare()
    stream.prepare()
    assert (replay.disjoint, stream.disjoint) == (False, True)
    for k in range(replay.pool_chunks):
        for a, b in zip(replay.chunk_data(k), stream.chunk_data(k)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), k
    xs3, _ = stream.chunk_data(3)
    assert not np.array_equal(np.asarray(xs3),
                              np.asarray(replay.chunk_data(3)[0]))


def test_stream_program_is_named():
    """The generator's program carries the name the stream reader counts."""
    stream = _traffic("stream")
    stream.prepare()
    text = stream._generate.lower(stream._w, stream._seed,
                                  np.int32(0)).as_text()
    assert f"@{generator.STREAM_PROGRAM}" in text


@pytest.mark.parametrize("name,flat", [("sec5.replay", False),
                                       ("sec5.stream", True)])
def test_eps_ledger_composition(name, flat):
    """The stream's rounds are disjoint, so the program's ledger is flat at
    eps (Theorem 1) and equals the reference's; a replayed pool composes
    sequentially."""
    cell = cells.tiny_cell(name, {"nodes": 16, "dim": 384},
                           {"chunk_rounds": 8})
    drv = linear.Driver(cell, cells.SEED)
    drv.prepare()
    done = []

    def stop(*args):
        done.append(1)
        if len(done) == drv.open_at:
            drv.keep(*args)
            return True
        return False
    drv.run(stop)
    eps = cell["config"]["spec"]["eps"]
    rounds = drv.open_at * drv.chunk_rounds
    ledger = np.asarray(drv.res.eps_ledger, np.float64)
    expect = gossip_omd.eps_ledger(eps, rounds, disjoint=flat)
    assert np.array_equal(ledger, expect)
    assert (len(set(ledger)) == 1) is flat
    values = drv.readings(drv.open_at)
    assert values["eps_gap"] == 0.0
    ok, checks = compare.judge(values, cell["config"]["limits"])
    assert ok, checks
