"""Node-axis sharding — shard_map over a ("node",) / ("seed","node") mesh.

Multi-device equivalence runs in subprocesses with 8 fake CPU devices
(XLA_FLAGS, same harness as tests/test_shard_seed.py). The contract:

  * node-sharded runs match dense `run()` within an ASSERTED float32
    reduction-order bound — Laplace noise on, delay in {0, 2}, both
    engines, m=10 on 4 devices (so the pad-to-12 rule is always live);
  * the sharded program is engine-agnostic: sim and dist sharded runs are
    BIT-identical to each other, and a sharded run re-executed under the
    same device count is bit-identical (determinism / resume anchor);
  * checkpoints cross device counts: 4 -> 1 and 1 -> 4;
  * the ("seed","node") grid matches per-seed sequential runs;
  * a node-sharded snapshot serves: verify_snapshot + batched predict;
  * each shard draws only its block of the noise rows (the
    ``shard.noise_rows_drawn`` gauge and the compiled program's shapes).

In-process tests cover the 1-device fallback, the error surfaces, the
mixer-to-sparse-graph lowering and the edge partitioner.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.api import RunSpec, run
from repro.api.mixers import (MIXERS, DelayedMixer, RingRollMixer,
                              SparseMixer)
from repro.api.shard_node import (partition_graph, resolve_node_mesh,
                                  sparse_graph_and_delay)
from repro.core.graph import SparseGraph, ring_edges
from repro.launch.mesh import make_mesh, node_mesh, seed_node_mesh

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = r"""
import numpy as np
from repro.api import RunSpec, run
from repro.api.runner import run_batch

ATOL = 5e-6      # float32 reduction-order bound, asserted on every field
FIELDS = ("final_w", "loss", "correct", "w_bar_loss", "sparsity")


def spec(**kw):
    base = dict(nodes=10, dim=8, horizon=14, eps=1.0, alpha0=0.5, lam=0.01,
                stream="drift", stream_options={"period": 7},
                mixer="sparse", mixer_options={"topology": "ring"})
    base.update(kw)
    return RunSpec(**base)


def assert_close(a, b, what, atol=ATOL):
    for f in FIELDS:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        d = np.abs(x - y).max()
        assert d <= atol, f"{what}: field {f} off by {d} (> {atol})"


def assert_identical(a, b, what):
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{what}: field {f} diverged")
"""


def _run(code: str, timeout=520) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    out = subprocess.run([sys.executable, "-c", _PRELUDE + code],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# -- multi-device equivalence (subprocesses, 8 fake devices) -----------------

@pytest.mark.slow
def test_node_sharded_matches_dense_and_engines_agree():
    """node_devices=4, m=10 (pads to 12): within the asserted bound of the
    dense run for both engines x delay {0, 2}, noise on — and the sharded
    sim/dist runs are BIT-identical to each other (shared round body)."""
    out = _run(r"""
import jax
assert jax.local_device_count() == 8
for delay in (0, 2):
    sharded = {}
    for engine in ("sim", "dist"):
        dense = run(spec(mixer="ring", mixer_options={}, delay=delay),
                    engine=engine, chunk_rounds=7, warmup=False,
                    compute_regret=False)
        sh = run(spec(delay=delay), engine=engine, chunk_rounds=7,
                 warmup=False, compute_regret=False, node_devices=4)
        assert_close(sh, dense, f"{engine}/delay={delay} sharded vs dense")
        np.testing.assert_array_equal(dense.eps_ledger, sh.eps_ledger)
        sharded[engine] = sh
        print(engine, delay, "OK")
    assert_identical(sharded["sim"], sharded["dist"],
                     f"delay={delay} sharded sim vs dist")
""")
    assert out.count("OK") == 4


@pytest.mark.slow
def test_node_sharded_deterministic_and_padding_exact():
    """Re-running under the same node count is bit-identical; m=8 on 8
    devices (block=1, no padding) and m=10 on 8 (pad 10->16) both hold the
    dense bound."""
    out = _run(r"""
a = run(spec(), chunk_rounds=7, warmup=False, compute_regret=False,
        node_devices=4)
b = run(spec(), chunk_rounds=7, warmup=False, compute_regret=False,
        node_devices=4)
assert_identical(a, b, "same-layout determinism")
for m in (8, 10):
    dense = run(spec(nodes=m, mixer="ring", mixer_options={}),
                chunk_rounds=7, warmup=False, compute_regret=False)
    sh = run(spec(nodes=m), chunk_rounds=7, warmup=False,
             compute_regret=False, node_devices=8)
    assert sh.final_w.shape == (m, 8)
    assert_close(sh, dense, f"m={m} on 8 devices")
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_checkpoint_crosses_node_device_counts():
    """Save under node_devices=4, resume under 1 (and 1 -> 4): state crossing
    the chunk boundary is global and unpadded, so resume continues exactly;
    same-layout save/resume is bit-identical to the uninterrupted run."""
    out = _run(r"""
import tempfile
sp = spec(delay=1, horizon=12)
full_sharded = run(sp, chunk_rounds=6, warmup=False, compute_regret=False,
                   node_devices=4)
full_dense = run(sp.replace(mixer="ring", mixer_options={}), chunk_rounds=6,
                 warmup=False, compute_regret=False)
# 4 devices -> 4 devices: bit-identical to the uninterrupted sharded run
ck = tempfile.mkdtemp()
run(sp, chunk_rounds=6, warmup=False, compute_regret=False, horizon=6,
    checkpoint_every=6, checkpoint_dir=ck, node_devices=4)
same = run(sp, chunk_rounds=6, warmup=False, compute_regret=False,
           checkpoint_dir=ck, resume=True, node_devices=4)
assert same.start_round == 6
np.testing.assert_array_equal(full_sharded.final_w, same.final_w)
# 4 devices -> 1 device (unsharded sparse): stays within the dense bound
down = run(sp, chunk_rounds=6, warmup=False, compute_regret=False,
           checkpoint_dir=ck, resume=True)
assert down.start_round == 6
assert np.abs(down.final_w - full_dense.final_w).max() <= ATOL
# 1 device -> 4 devices
ck2 = tempfile.mkdtemp()
run(sp, chunk_rounds=6, warmup=False, compute_regret=False, horizon=6,
    checkpoint_every=6, checkpoint_dir=ck2)
up = run(sp, chunk_rounds=6, warmup=False, compute_regret=False,
         checkpoint_dir=ck2, resume=True, node_devices=4)
assert up.start_round == 6
assert np.abs(up.final_w - full_dense.final_w).max() <= ATOL
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_seed_node_grid_matches_sequential():
    """run_batch over the ("seed","node") grid (2 x 4 devices): every seed
    within the bound of its sequential run(); the grid result is
    bit-identical across seed-device counts (1x4 vs 2x4) because node
    reduction order is fixed by the node count alone."""
    out = _run(r"""
seeds = [0, 1, 2]
grid = run_batch(spec(), seeds, chunk_rounds=7, warmup=False,
                 compute_regret=False, devices=2, node_devices=4)
assert grid[0].metrics["batch"]["devices"] == 2
narrow = run_batch(spec(), seeds, chunk_rounds=7, warmup=False,
                   compute_regret=False, node_devices=4)
for s, g, nv in zip(seeds, grid, narrow):
    seq = run(spec().replace(seed=s), chunk_rounds=7, warmup=False,
              compute_regret=False)
    assert_close(g, seq, f"grid seed={s} vs sequential")
    assert_identical(g, nv, f"seed={s}: 2x4 vs 1x4 grid")
# delay + dist engine over the grid
for r, s in zip(run_batch(spec(delay=2), seeds, engine="dist",
                          chunk_rounds=7, warmup=False,
                          compute_regret=False, devices=2, node_devices=4),
                seeds):
    seq = run(spec(delay=2).replace(seed=s), engine="dist", chunk_rounds=7,
              warmup=False, compute_regret=False)
    assert_close(r, seq, f"dist/delay=2 grid seed={s}")
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_node_sharded_snapshot_serves():
    """repro.serve on a node-sharded trainer: verify_snapshot replays the
    sharded layout bit-identically (and bounds the dense cross-check), and
    the batched predict path serves the sharded model's rows."""
    out = _run(r"""
import jax.numpy as jnp
from repro.serve.state import (make_predict_fn, snapshot_from_state,
                               verify_snapshot)
sp = spec()
res = run(sp, chunk_rounds=7, warmup=False, compute_regret=False,
          node_devices=4)
snap = snapshot_from_state(sp, "sim", res.final_state, version=1,
                           eps_spent=1.0)
assert snap.round == 14
np.testing.assert_array_equal(snap.w, res.final_w)
assert verify_snapshot(sp, "sim", snap, node_devices=4)          # bit replay
assert verify_snapshot(sp, "sim", snap, atol=ATOL)               # dense bound
assert not verify_snapshot(sp, "sim", snap)                      # dense bits differ
predict = make_predict_fn("node")
feats = jnp.ones((5, sp.dim), jnp.float32)
nodes = jnp.array([0, 3, 9, 9, 1])
margins, labels = predict(snap.w, snap.w_bar, feats, nodes)
ref = np.asarray(res.final_w).sum(axis=1)[np.asarray(nodes)]
np.testing.assert_allclose(np.asarray(margins), ref, atol=1e-6)
assert set(np.asarray(labels)) <= {-1.0, 1.0}
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_sharded_round_draws_only_its_rows():
    """On a 4-device mesh each shard draws only its block of the noise: the
    gauge ``shard.noise_rows_drawn`` reads ``block`` (m with a mechanism
    that draws whole), and the compiled chunk program holds no (m, n)
    random-bits array, only (block, n) ones."""
    out = _run(r"""
import jax
from repro import obs
from repro.api.shard_node import make_node_chunk_fn
from repro.launch.mesh import node_mesh
mesh = node_mesh(4)
for mech, rows in (("laplace", 3), ("gaussian", 10), ("none", 0)):
    tel = obs.Telemetry()
    sp = spec(nodes=10, dim=8, mechanism=mech)
    chunk_fn, init_fn = make_node_chunk_fn(sp, "sim", mesh, obs=tel)
    assert tel.metrics.snapshot()["shard.noise_rows_drawn"] == rows, mech
tel = obs.enable()
try:
    chunk_fn, init_fn = make_node_chunk_fn(spec(nodes=10, dim=8), "dist", mesh)
    assert tel.metrics.snapshot()["shard.noise_rows_drawn"] == 3
finally:
    obs.disable()
xs, ys = spec(nodes=10, dim=8).resolve_stream().chunk(0, 2)
hlo = jax.jit(chunk_fn).lower(init_fn(jax.random.PRNGKey(0)), xs, ys
                              ).compile().as_text()
assert "u32[3,8]" in hlo and "u32[10,8]" not in hlo and "u32[12,8]" not in hlo
print("OK")
""")
    assert "OK" in out


# -- 1-device behavior (in-process) ------------------------------------------

def _spec(**kw):
    base = dict(nodes=10, dim=8, horizon=10, eps=1.0, alpha0=0.5, lam=0.01,
                stream="drift", stream_options={"period": 7},
                mixer="sparse", mixer_options={"topology": "ring"})
    base.update(kw)
    return RunSpec(**base)


def test_node_mesh_single_device_fallback():
    import jax
    if jax.local_device_count() != 1:
        pytest.skip("needs the default 1-device test process")
    assert node_mesh(None) is None
    assert node_mesh(0) is None
    assert node_mesh(1) is None
    assert node_mesh("auto") is None
    assert seed_node_mesh(1, "auto") is None
    assert seed_node_mesh(1, 1) is None


def test_node_mesh_too_many_devices_errors():
    import jax
    want = jax.local_device_count() + 1
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        node_mesh(want)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        seed_node_mesh(jax.local_device_count(), 2)


def test_run_node_devices_one_is_the_plain_path():
    plain = run(_spec(), chunk_rounds=5, warmup=False, compute_regret=False)
    fallback = run(_spec(), chunk_rounds=5, warmup=False,
                   compute_regret=False, node_devices=1)
    np.testing.assert_array_equal(plain.final_w, fallback.final_w)
    np.testing.assert_array_equal(np.asarray(plain.loss),
                                  np.asarray(fallback.loss))


def test_one_device_node_mesh_runs_the_sharded_program():
    """An explicit 1-device ("node",) mesh exercises shard_map + halo code
    in-process and stays within the bound of the unsharded sparse run."""
    sharded = run(_spec(), chunk_rounds=5, warmup=False,
                  compute_regret=False, node_mesh=make_mesh((1,), ("node",)))
    plain = run(_spec(), chunk_rounds=5, warmup=False, compute_regret=False)
    assert np.abs(sharded.final_w - plain.final_w).max() <= 5e-6
    assert np.abs(np.asarray(sharded.w_bar_loss)
                  - np.asarray(plain.w_bar_loss)).max() <= 5e-6


def test_resolve_node_mesh_error_surfaces():
    with pytest.raises(ValueError, match="'node' axis"):
        resolve_node_mesh(None, make_mesh((1,), ("seed",)))
    assert resolve_node_mesh(None, None) is None
    assert resolve_node_mesh(1, None) is None


def test_run_batch_rejects_node_mesh_without_seed_axis():
    from repro.api.runner import run_batch
    with pytest.raises(ValueError, match="seed"):
        run_batch(_spec(), (0, 1), mesh=make_mesh((1,), ("node",)),
                  chunk_rounds=5, warmup=False)


# -- mixer lowering / partitioner units --------------------------------------

def test_sparse_graph_and_delay_unwraps_mixers():
    g, d = sparse_graph_and_delay(SparseMixer(graph=ring_edges(6)))
    assert d == 0 and g.m == 6
    g, d = sparse_graph_and_delay(
        DelayedMixer(inner=SparseMixer(graph=ring_edges(6)), delay=3))
    assert d == 3 and g.m == 6
    # RingRollMixer lowers to its exact edge-list form
    g, d = sparse_graph_and_delay(RingRollMixer(m=8, self_weight=0.3))
    from repro.core.graph import ring_matrix
    np.testing.assert_array_equal(g.to_dense(), ring_matrix(8, 0.3))
    # fixed dense single-matrix stacks convert; schedules refuse
    g, _ = sparse_graph_and_delay(MIXERS.build("hypercube", m=8))
    assert g.edges > 0
    with pytest.raises(ValueError, match="time-varying"):
        sparse_graph_and_delay(MIXERS.build("time_varying", m=8))
    with pytest.raises(ValueError, match="node-sharded"):
        sparse_graph_and_delay(MIXERS.build("het_delayed", m=8, delay=2))
    with pytest.raises(ValueError, match="node-sharded"):
        sparse_graph_and_delay(MIXERS.build("disconnected", m=8))


@pytest.mark.parametrize("devices", [1, 2, 3, 4])
def test_partition_reassembles_to_the_dense_matrix(devices):
    g = SparseGraph.make("ring", 10)
    part = partition_graph(g, devices)
    assert part.block * devices == part.m_pad >= 10
    A = np.zeros((part.m_pad, part.m_pad), np.float32)
    for o, dl, sl, ww in part.offsets:
        for d in range(devices):
            s = (d + o) % devices
            np.add.at(A, (dl[d] + d * part.block, sl[d] + s * part.block),
                      ww[d])
    np.testing.assert_array_equal(A[:10, :10], g.to_dense())
    assert np.all(A[10:] == 0) and np.all(A[:, 10:] == 0)
    np.testing.assert_array_equal(part.diag_blocks.ravel()[:10], g.diag())


def test_partition_rejects_zero_devices():
    with pytest.raises(ValueError, match="devices"):
        partition_graph(ring_edges(4), 0)
