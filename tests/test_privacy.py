import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import MECHANISMS
from repro.core.privacy import (
    PrivacyAccountant, PrivacyConfig, clip_by_l2, laplace_scale,
    row_block_counters, rows_drawable, sample_laplace, sample_laplace_rows,
    sample_laplace_tree, sensitivity,
)


def test_sensitivity_lemma1():
    # S(t) <= 2 alpha sqrt(n) L
    assert float(sensitivity(0.1, 100, 1.0)) == pytest.approx(2 * 0.1 * 10 * 1.0)


def test_laplace_scale_eq8():
    assert float(laplace_scale(0.1, 100, 1.0, 0.5)) == pytest.approx(2 * 0.1 * 10 / 0.5)
    assert float(laplace_scale(0.1, 100, 1.0, math.inf)) == 0.0


def test_laplace_empirical_scale():
    key = jax.random.PRNGKey(0)
    b = 2.5
    x = sample_laplace(key, (200_000,), b)
    # Laplace(b): E|x| = b, Var = 2 b^2
    assert float(jnp.mean(jnp.abs(x))) == pytest.approx(b, rel=0.02)
    assert float(jnp.var(x)) == pytest.approx(2 * b * b, rel=0.05)


def test_laplace_zero_scale_is_zero():
    x = sample_laplace(jax.random.PRNGKey(1), (100,), 0.0)
    assert float(jnp.max(jnp.abs(x))) == 0.0


def test_laplace_tree_independent_leaves():
    tree = {"a": jnp.zeros((20_000,)), "b": jnp.zeros((20_000,))}
    noise = sample_laplace_tree(jax.random.PRNGKey(2), tree, 1.0)
    corr = np.corrcoef(np.asarray(noise["a"]), np.asarray(noise["b"]))[0, 1]
    assert abs(corr) < 0.05


@pytest.mark.parametrize("norm_target", [
    0.01, 0.1, 0.5, 1.0, 2.0, 5.0, 8.0, 9.0, 9.5, 10.0,
])
def test_clip_by_l2(norm_target):
    tree = {"w": jnp.full((64,), 2.0), "b": jnp.full((8,), -1.0)}
    clipped, pre = clip_by_l2(tree, norm_target)
    post = math.sqrt(sum(float(jnp.sum(jnp.square(l))) for l in jax.tree.leaves(clipped)))
    assert post <= norm_target * (1 + 1e-5)
    if float(pre) <= norm_target:  # no-op when already inside the ball
        np.testing.assert_allclose(np.asarray(clipped["w"]), 2.0, rtol=1e-6)


def test_privacy_config_coordinate_style():
    cfg = PrivacyConfig(eps=1.0, L=1.0, clip_style="coordinate")
    # per-coordinate scale has no sqrt(n) factor
    assert float(cfg.scale_for(0.1, 10_000)) == pytest.approx(0.2)
    g = PrivacyConfig(eps=1.0, L=1.0, clip_style="global")
    assert float(g.scale_for(0.1, 10_000)) == pytest.approx(0.2 * 100)


def test_accountant_parallel_composition():
    acc = PrivacyAccountant(eps_per_round=0.5)
    for _ in range(100):
        acc.step()
    assert acc.guarantee == 0.5  # Thm 1: disjoint rounds don't compound
    seq = PrivacyAccountant(eps_per_round=0.5, disjoint_streams=False)
    seq.step(100)
    assert seq.guarantee == pytest.approx(50.0)


def test_accountant_zero_rounds_guarantees_zero():
    """Fix: before the first broadcast NOTHING has been released, so the
    guarantee is 0 — the old code claimed eps_per_round at rounds == 0."""
    assert PrivacyAccountant(eps_per_round=0.5).guarantee == 0.0
    assert PrivacyAccountant(eps_per_round=0.5,
                             disjoint_streams=False).guarantee == 0.0


def test_accountant_guarantee_at_trajectory():
    par = PrivacyAccountant(eps_per_round=0.25)
    assert [par.guarantee_at(t) for t in (0, 1, 7, 10_000)] == \
        [0.0, 0.25, 0.25, 0.25]
    seq = PrivacyAccountant(eps_per_round=0.25, disjoint_streams=False)
    assert [seq.guarantee_at(t) for t in (0, 1, 4)] == [0.0, 0.25, 1.0]


def test_accountant_ledger():
    par = PrivacyAccountant(eps_per_round=2.0)
    par.step(3)
    assert par.ledger() == [2.0, 2.0, 2.0]
    seq = PrivacyAccountant(eps_per_round=2.0, disjoint_streams=False)
    seq.step(2)
    assert seq.ledger() == [2.0, 4.0]
    assert seq.ledger(rounds=4) == [2.0, 4.0, 6.0, 8.0]


def test_accountant_rejects_invalid_input():
    with pytest.raises(ValueError):
        PrivacyAccountant(eps_per_round=-1.0)
    with pytest.raises(ValueError):
        PrivacyAccountant(eps_per_round=1.0, rounds=-3)
    acc = PrivacyAccountant(eps_per_round=1.0)
    with pytest.raises(ValueError):
        acc.step(-1)
    assert acc.rounds == 0  # the failed step must not half-apply


# -- row blocks of one Laplace draw (the node-sharded round's noise) ---------

def _full_rows(full, row0, rows):
    """Rows [row0, row0 + rows) of ``full``, zeros past its end (numpy)."""
    full = np.asarray(full)
    out = np.zeros((rows,) + full.shape[1:], full.dtype)
    part = full[row0:row0 + rows]
    out[:len(part)] = part
    return out


@pytest.mark.parametrize("m,n,devices", [(10, 7, 4), (8, 10000, 4),
                                         (13, 129, 3)])
def test_sample_laplace_rows_is_the_full_draw_sliced(m, n, devices):
    """Every shard's block, drawn alone under jit with a traced row0 and
    under vmap over keys, equals the full draw's rows bit for bit; the
    last shard's padding rows are exactly 0."""
    block = -(-m // devices)
    key = jax.random.PRNGKey(2 ** 31 + 77)
    assert rows_drawable(key, (m, n))
    full = sample_laplace(key, (m, n), 0.7)
    draw = jax.jit(lambda k, r0: sample_laplace_rows(k, (m, n), r0, block,
                                                     0.7))
    keys = jax.random.split(key, 3)
    fulls = [sample_laplace(k, (m, n), 0.7) for k in keys]
    batched = jax.jit(jax.vmap(draw, in_axes=(0, None)))
    for d in range(devices):
        row0 = jnp.int32(d * block)
        got = np.asarray(draw(key, row0))
        np.testing.assert_array_equal(got, _full_rows(full, d * block, block))
        pad = max(0, (d + 1) * block - m)
        if pad:
            assert not np.any(got[block - pad:])
        for f, g in zip(fulls, np.asarray(batched(keys, row0))):
            np.testing.assert_array_equal(g, _full_rows(f, d * block, block))


@pytest.mark.parametrize("row0,rows,n", [
    (0, 3, 7),
    (42_946, 4, 100_003),           # 2**32 falls inside row 42,948
    (2 ** 31 - 3, 2, 65_537),       # high word ~2**15
    (429, 2, 10_000_019),           # the paper's widest n range
])
def test_row_block_counters_carry_into_the_high_word(row0, rows, n):
    """The threefry counters are the 64-bit flat indices (row0 + r) * n + j,
    checked against numpy uint64 without drawing the (m, n) sample."""
    hi, lo = jax.jit(row_block_counters, static_argnums=(1, 2))(
        jnp.int32(row0), rows, n)
    flat = ((np.uint64(row0) + np.arange(rows, dtype=np.uint64))[:, None]
            * np.uint64(n) + np.arange(n, dtype=np.uint64)[None, :])
    np.testing.assert_array_equal(np.asarray(hi), (flat >> 32).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(lo),
                                  (flat & 0xFFFFFFFF).astype(np.uint32))


@pytest.mark.parametrize("b", [1, 0xFFFF, 0x10000, 10 ** 8, 2 ** 32 - 1])
def test_mul_wide_is_the_64_bit_product(b):
    from repro.core.privacy import _mul_wide
    a = np.concatenate([np.array([0, 1, 0xFFFF, 0x10000, 2 ** 32 - 1],
                                 np.uint64),
                        np.random.default_rng(b).integers(
                            0, 2 ** 32, 64, dtype=np.uint64)])
    hi, lo = _mul_wide(jnp.asarray(a.astype(np.uint32)), b)
    want = [int(x) * b for x in a]
    assert [(int(h) << 32) | int(l) for h, l in zip(hi, lo)] == want


def test_sample_laplace_rows_falls_back_exactly():
    """Without partitionable threefry, or with a typed key, the block is
    the full draw sliced — the same numbers either way."""
    m, n, rows = 10, 7, 3
    key = jax.random.PRNGKey(5)
    prev = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", False)
    try:
        assert not rows_drawable(key, (m, n))
        full = sample_laplace(key, (m, n), 1.3)
        for row0 in (0, 3, 9):
            got = jax.jit(sample_laplace_rows, static_argnums=(1, 3))(
                key, (m, n), jnp.int32(row0), rows, 1.3)
            np.testing.assert_array_equal(np.asarray(got),
                                          _full_rows(full, row0, rows))
        assert MECHANISMS.build("laplace").rows_drawn(key, (m, n), rows) == m
    finally:
        jax.config.update("jax_threefry_partitionable", prev)
    typed = jax.random.key(5)
    assert not rows_drawable(typed, (m, n))
    got = sample_laplace_rows(typed, (m, n), 9, rows, 1.3)
    np.testing.assert_array_equal(
        np.asarray(got), _full_rows(sample_laplace(typed, (m, n), 1.3), 9, rows))


@pytest.mark.parametrize("name,drawn", [("laplace", 3), ("gaussian", 10),
                                        ("none", 0)])
def test_mechanism_sample_rows_matches_sample(name, drawn):
    """Each mechanism's block form is its full sample's rows, bit for bit,
    and ``rows_drawn`` says how many rows it draws for the block."""
    mech = MECHANISMS.build(name)
    key = jax.random.PRNGKey(11)
    full = mech.sample(key, (10, 6), 0.4)
    for row0 in (0, 3, 6, 9):
        got = mech.sample_rows(key, (10, 6), row0, 3, 0.4)
        np.testing.assert_array_equal(np.asarray(got),
                                      _full_rows(full, row0, 3))
    assert mech.rows_drawn(key, (10, 6), 3) == drawn
