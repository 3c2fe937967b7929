"""The BACKENDS registry and the fused Pallas round body vs the reference.

The contract (docs/kernels.md):

  * `RunSpec(backend="pallas")` matches the reference backend per-field on
    every STREAMS scenario, both engines, Laplace noise ON, delay rings in
    {0, 2}: `correct` / `sparsity` / `eps_ledger` bit-exact (the noise is
    sampled outside the kernel from the identical PRNG stream), float
    trajectories within the f32 reduction-order bound;
  * the kernels themselves hold on odd shapes — dims not multiples of the
    128-lane tile, node counts not multiples of the 8-row sublane — via
    explicit zero-padding (`tests` drive `round_stats` / `round_update` /
    `dual_step` directly against jnp oracles);
  * checkpoints are backend-portable: pallas resumes from a reference
    checkpoint (and vice versa) bit-identically, because init and state
    layout are backend-independent;
  * unsupported specs fail loudly, naming the reference fallback.

Multi-device (node-sharded) pallas equivalence runs in a subprocess with
8 fake CPU devices, same harness as tests/test_shard_node.py.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import BACKENDS, ExecConfig, PallasBackend, RunSpec, run
from repro.api.backends import pallas_supported
from repro.api.registry import UnknownEntryError
from repro.api.runner import run_batch
from repro.kernels.round_fused import (dual_step, node_sum, round_stats,
                                       round_update, _pad_cols, _pad_rows)

ATOL = 5e-6      # float32 reduction-order bound for float trajectories
EXACT = ("correct", "sparsity", "eps_ledger")
CLOSE = ("final_w", "loss", "w_bar_loss")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = ExecConfig(chunk_rounds=3, warmup=False, compute_regret=False)


def _spec(**kw):
    base = dict(nodes=6, dim=40, horizon=6, eps=1.0, alpha0=0.5, lam=0.01,
                stream="drift", stream_options={"period": 3},
                mixer="sparse", mixer_options={"topology": "ring"})
    base.update(kw)
    return RunSpec(**base)


def assert_backends_agree(ref, pal, what):
    for f in EXACT:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), np.asarray(getattr(pal, f)),
            err_msg=f"{what}: field {f} must be bit-exact")
    for f in CLOSE:
        d = np.abs(np.asarray(getattr(ref, f))
                   - np.asarray(getattr(pal, f))).max()
        assert d <= ATOL, f"{what}: field {f} off by {d} (> {ATOL})"


# -- registry ----------------------------------------------------------------

def test_backends_registry_names_and_describe():
    assert set(BACKENDS.names()) >= {"reference", "pallas"}
    desc = BACKENDS.describe()
    assert "pallas" in desc and desc["pallas"]


def test_unknown_backend_names_available():
    with pytest.raises(UnknownEntryError, match="pallas"):
        run(_spec(backend="nope"), exec=CFG)


def test_backend_options_typo_raises():
    with pytest.raises(TypeError, match="mode"):
        run(_spec(backend="pallas", backend_options={"moed": "auto"}),
            exec=CFG)


def test_backend_instance_passes_through():
    be = PallasBackend(mode="hybrid")
    res = run(_spec(backend=be), exec=CFG)
    ref = run(_spec(), exec=CFG)
    assert_backends_agree(ref, res, "instance backend")


# -- equivalence: streams x engines x delay, noise on ------------------------

@pytest.mark.parametrize("stream", ["social_sparse", "drift",
                                    "heterogeneous", "bursty"])
@pytest.mark.parametrize("engine", ["sim", "dist"])
def test_pallas_matches_reference_all_streams(stream, engine):
    spec = _spec(stream=stream,
                 stream_options={"period": 3} if stream == "drift" else {})
    ref = run(spec, engine=engine, exec=CFG)
    pal = run(spec.replace(backend="pallas"), engine=engine, exec=CFG)
    assert_backends_agree(ref, pal, f"{stream}/{engine}")


@pytest.mark.parametrize("delay", [0, 2])
@pytest.mark.parametrize("engine", ["sim", "dist"])
@pytest.mark.parametrize("mode", ["fused", "hybrid"])
def test_pallas_modes_match_reference_with_delay(delay, engine, mode):
    spec = _spec(delay=delay)
    ref = run(spec, engine=engine, exec=CFG)
    pal = run(spec.replace(backend="pallas",
                           backend_options={"mode": mode}),
              engine=engine, exec=CFG)
    assert_backends_agree(ref, pal, f"mode={mode}/{engine}/delay={delay}")


def test_pallas_matches_reference_under_faults():
    """Fault schedules force the hybrid path (time-varying mixing stays in
    XLA); crashes exercise the in-kernel alive-freeze mask."""
    spec = _spec(horizon=8, faults="links",
                 faults_options={"link_rate": 0.3, "seed": 1})
    ref = run(spec, exec=CFG)
    pal = run(spec.replace(backend="pallas"), exec=CFG)
    assert_backends_agree(ref, pal, "link faults")
    from repro.faults import FaultSpec
    crash = _spec(horizon=8, faults=FaultSpec(crashes=((2, 3, 6),)))
    ref = run(crash, exec=CFG)
    pal = run(crash.replace(backend="pallas"), exec=CFG)
    assert_backends_agree(ref, pal, "crash faults")
    np.testing.assert_array_equal(ref.connectivity, pal.connectivity)


def test_pallas_run_batch_matches_reference():
    seeds = [0, 1]
    ref = run_batch(_spec(), seeds, exec=CFG)
    pal = run_batch(_spec(backend="pallas"), seeds, exec=CFG)
    for s, (r, p) in enumerate(zip(ref, pal)):
        assert_backends_agree(r, p, f"run_batch seed {s}")


def test_fused_mode_refuses_what_it_cannot_fuse():
    with pytest.raises(ValueError, match="hybrid"):
        run(_spec(faults="links", faults_options={"link_rate": 0.1},
                  backend="pallas", backend_options={"mode": "fused"}),
            exec=CFG)


def test_pallas_rejects_unsupported_spec():
    spec = _spec(backend="pallas", local_rule="rda")
    if pallas_supported(spec):      # rda may one day lower; guard intent
        pytest.skip("rda became pallas-supported")
    with pytest.raises(ValueError, match="reference"):
        run(spec, exec=CFG)


# -- checkpoint portability --------------------------------------------------

@pytest.mark.parametrize("engine", ["sim", "dist"])
def test_pallas_checkpoint_resume_bit_stable(tmp_path, engine):
    """A pallas run checkpointed mid-horizon resumes bit-identically to its
    own uninterrupted run — and a REFERENCE run can resume from the pallas
    checkpoint (state layout is backend-independent)."""
    spec = _spec(horizon=12, backend="pallas")
    full = run(spec, engine=engine, exec=CFG.replace(chunk_rounds=4))
    d = str(tmp_path / "ckpt")
    run(spec, engine=engine, horizon=8,
        exec=CFG.replace(chunk_rounds=4, checkpoint_every=8,
                         checkpoint_dir=d))
    res = run(spec, engine=engine,
              exec=CFG.replace(chunk_rounds=4, checkpoint_dir=d,
                               resume=True))
    assert res.start_round == 8
    np.testing.assert_array_equal(res.final_w, full.final_w)
    cross = run(spec.replace(backend="reference"), engine=engine,
                exec=CFG.replace(chunk_rounds=4, checkpoint_dir=d,
                                 resume=True))
    d2 = np.abs(np.asarray(cross.final_w) - np.asarray(full.final_w)).max()
    assert d2 <= ATOL


# -- kernel property tests: odd shapes vs jnp oracles ------------------------

def _padded(a, m_pad, n_pad):
    m, n = a.shape
    return jnp.pad(a, ((0, m_pad - m), (0, n_pad - n)))


@pytest.mark.parametrize("m,n", [(3, 40), (8, 128), (10, 200), (6, 1025),
                                 (17, 64)])
def test_round_stats_odd_shapes(m, n):
    """Soft-threshold stats on zero-padded blocks match the row-wise jnp
    math on the unpadded arrays — padding rows/cols contribute nothing."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(m * n))
    theta = jax.random.normal(k1, (m, n))
    x = jax.random.normal(k2, (m, n)) / np.sqrt(n)
    lam_t = 0.37
    m_pad, n_pad = _pad_rows(m), _pad_cols(n)
    dot, xsq, nnz, wbdot, wsum = round_stats(
        _padded(theta, m_pad, n_pad), _padded(x, m_pad, n_pad),
        jnp.float32(lam_t), m, interpret=True)
    w = jnp.sign(theta) * jnp.maximum(jnp.abs(theta) - lam_t, 0.0)
    np.testing.assert_allclose(np.asarray(dot[:m]),
                               np.asarray(jnp.sum(w * x, axis=1)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(xsq[:m]),
                               np.asarray(jnp.sum(x * x, axis=1)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(nnz[:m]),
                                  np.asarray(jnp.sum(w != 0, axis=1),
                                             np.float32))
    w_bar = jnp.mean(w, axis=0)
    np.testing.assert_allclose(np.asarray(wbdot[:m]),
                               np.asarray(jnp.sum(w_bar[None] * x, axis=1)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(wsum[:n]),
                               np.asarray(jnp.sum(w, axis=0)),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(wsum[n:]).max(initial=0.0)) == 0.0


@pytest.mark.parametrize("m,n", [(4, 40), (10, 130)])
@pytest.mark.parametrize("use_recv", [0.0, 1.0])
def test_round_update_odd_shapes(m, n, use_recv):
    keys = jax.random.split(jax.random.PRNGKey(7 * m + n), 6)
    theta = jax.random.normal(keys[0], (m, n))
    delta = 0.1 * jax.random.normal(keys[1], (m, n))
    x = jax.random.normal(keys[2], (m, n)) / np.sqrt(n)
    recv = jax.random.normal(keys[3], (m, n))
    coeff = jax.random.normal(keys[4], (m,))
    A = jax.nn.softmax(jax.random.normal(keys[5], (m, m)), axis=1)
    diag = jnp.diagonal(A)
    alive = jnp.ones((m,), jnp.float32).at[1].set(0.0)
    m_pad, n_pad = _pad_rows(m), _pad_cols(n)
    pad1 = lambda v: jnp.pad(v, (0, m_pad - m))
    theta_next, tilde = round_update(
        _padded(A, m_pad, m_pad), _padded(theta, m_pad, n_pad),
        _padded(delta, m_pad, n_pad), _padded(x, m_pad, n_pad),
        _padded(recv, m_pad, n_pad), pad1(coeff), pad1(diag), pad1(alive),
        jnp.float32(0.25), jnp.float32(use_recv), noise_self=True,
        interpret=True)
    tilde_ref = theta + delta
    r = recv if use_recv else tilde_ref
    mixed = A @ r + diag[:, None] * (tilde_ref - r)
    want = mixed - 0.25 * coeff[:, None] * x
    want = jnp.where(alive[:, None] > 0, want, theta)
    np.testing.assert_allclose(np.asarray(theta_next[:m, :n]),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(tilde[:m, :n]),
                               np.asarray(tilde_ref), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("m,n", [(5, 40), (8, 384)])
def test_dual_step_odd_shapes(m, n):
    keys = jax.random.split(jax.random.PRNGKey(m + n), 4)
    mixed = jax.random.normal(keys[0], (m, n))
    x = jax.random.normal(keys[1], (m, n))
    theta = jax.random.normal(keys[2], (m, n))
    coeff = jax.random.normal(keys[3], (m,))
    alive = jnp.ones((m,), jnp.float32).at[0].set(0.0)
    m_pad, n_pad = _pad_rows(m), _pad_cols(n)
    out = dual_step(_padded(mixed, m_pad, n_pad), _padded(x, m_pad, n_pad),
                    _padded(theta, m_pad, n_pad),
                    jnp.pad(coeff, (0, m_pad - m)),
                    jnp.pad(alive, (0, m_pad - m)),
                    jnp.float32(0.5), interpret=True)
    want = jnp.where(alive[:, None] > 0,
                     mixed - 0.5 * coeff[:, None] * x, theta)
    np.testing.assert_allclose(np.asarray(out[:m, :n]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m", [6, 64, 1100])
def test_node_sum_matches_sum(m):
    v = jax.random.uniform(jax.random.PRNGKey(m), (m,))
    got = node_sum(v, interpret=True)
    np.testing.assert_allclose(float(got), float(jnp.sum(v)), rtol=1e-6)
    batch = jax.vmap(lambda u: node_sum(u, interpret=True))(
        jnp.stack([v, 2 * v]))
    assert float(batch[0]) == float(got)


def test_round_stats_rejects_unpadded():
    with pytest.raises(ValueError, match="padded"):
        round_stats(jnp.zeros((3, 40)), jnp.zeros((3, 40)),
                    jnp.float32(0.1), 3, interpret=True)


def test_f32_scalar_schedule():
    """alpha_t / lam_t arrive as traced f32 scalars from the OMD schedule —
    the kernels must accept them without retracing per round."""
    spec = _spec(horizon=4, backend="pallas")
    res = run(spec, exec=CFG.replace(chunk_rounds=2))
    assert res.rounds == 4 and np.isfinite(np.asarray(res.loss)).all()


# -- node-sharded pallas (subprocess, 8 fake devices) ------------------------

@pytest.mark.slow
def test_node_sharded_pallas_matches_reference():
    """backend="pallas" under node_devices=4 (m=10 pads to 12): per-shard
    stats kernels + psum'd w_bar must match the unsharded reference within
    the same bound as the reference sharded path, and stay engine-agnostic."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    code = r"""
import numpy as np
from repro.api import ExecConfig, RunSpec, run

ATOL = 5e-6
cfg = ExecConfig(chunk_rounds=7, warmup=False, compute_regret=False)

def spec(**kw):
    base = dict(nodes=10, dim=8, horizon=14, eps=1.0, alpha0=0.5, lam=0.01,
                stream="drift", stream_options={"period": 7},
                mixer="sparse", mixer_options={"topology": "ring"})
    base.update(kw)
    return RunSpec(**base)

for engine in ("sim", "dist"):
    ref = run(spec(), engine=engine, exec=cfg)
    pal = run(spec(backend="pallas"), engine=engine,
              exec=cfg.replace(node_devices=4))
    for f in ("final_w", "loss", "correct", "w_bar_loss", "sparsity"):
        d = np.abs(np.asarray(getattr(ref, f))
                   - np.asarray(getattr(pal, f))).max()
        assert d <= ATOL, f"{engine}: {f} off by {d}"
    np.testing.assert_array_equal(ref.eps_ledger, pal.eps_ledger)
    print(engine, "OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=520, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.count("OK") == 2


# -- mode selection under the VMEM budget ------------------------------------

@pytest.mark.parametrize("nodes,mode,want", [
    (64, "auto", "fused"),        # §V: A is 16 KiB
    (1024, "auto", "fused"),      # the update kernel fits at 128 lanes
    (1100, "auto", "hybrid"),     # A no longer fits beside the streams
    (1100, "hybrid", "hybrid"),
])
def test_auto_mode_follows_vmem_budget(nodes, mode, want):
    spec = RunSpec(nodes=nodes, dim=10_000, horizon=4, backend="pallas")
    assert _mode(PallasBackend(mode=mode), spec) == want


def _mode(backend, spec):
    plan = backend._dense_plan(spec, spec.resolve_mixer())
    return "hybrid" if plan is None else "fused"


def test_budget_refusals_name_the_limit():
    spec = RunSpec(nodes=1100, dim=10_000, horizon=4, backend="pallas")
    with pytest.raises(ValueError, match="scoped VMEM limit"):
        _mode(PallasBackend(mode="fused"), spec)
    with pytest.raises(ValueError, match="scoped VMEM limit"):
        _mode(PallasBackend(), spec.replace(nodes=4096))
    faulty = _spec(faults="links", faults_options={"link_rate": 0.1})
    assert _mode(PallasBackend(), faulty) == "hybrid"
