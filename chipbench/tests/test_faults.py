"""A sound run comes out correct; a run with the timed path broken underneath
comes out not correct, once for each fault a cell can have."""
import jax
import pytest

from chipbench.tests import cells

CELLS = {"sec5.replay": cells.sec5_tiny, "sec5.stream": cells.sec5_stream_tiny,
         "ring64k.sharded4": cells.ring64k_tiny}


def _wrap_chunk_program(monkeypatch, sharded: bool, wrap):
    """Wrap the chunk program `repro.api.run` builds with ``wrap(fn)``."""
    if sharded:
        import repro.api.shard_node as mod
        name = "make_node_chunk_fn"
    else:
        import repro.api.runner as mod
        name = "make_chunk_fn"
    orig = getattr(mod, name)

    def patched(*args, **kw):
        fn, other = orig(*args, **kw)
        return wrap(fn), other
    monkeypatch.setattr(mod, name, patched)


def frozen(fn):
    """The step returns its state unchanged."""
    def chunk(state, xs, ys):
        _, outs = fn(state, xs, ys)
        return state, outs
    return chunk


def half_batch(fn):
    """Half of the nodes' samples are left out (their rows zeroed), and the
    learner runs on the rest."""
    def chunk(state, xs, ys):
        return fn(state, xs.at[:, 1::2].set(0.0), ys)
    return chunk


def altered(fn):
    """One answer is altered where it is produced: one node's loss in one
    round, by a thousandth."""
    def chunk(state, xs, ys):
        state, outs = fn(state, xs, ys)
        return state, outs._replace(loss=outs.loss.at[-1, 0].add(1e-3))
    return chunk


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name):
    out = cells.run(CELLS[name]())
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1
    assert set(out["metrics"]) >= {"samples_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [frozen, half_batch, altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", sorted(CELLS))
def test_fault_is_caught(monkeypatch, name, fault):
    cell = CELLS[name]()
    _wrap_chunk_program(monkeypatch, cell["chips"] > 1, fault)
    out = cells.run(cell)
    assert not out["correct"], out["checks"]


def test_missing_exchange_is_caught(monkeypatch):
    """The exchange between chips is left out: every ppermute of the halo
    returns the shard's own block."""
    import repro.api.shard_node as sn

    real = jax.lax

    class NoExchange:
        def __getattr__(self, name):
            if name == "ppermute":
                return lambda x, axis_name, perm: x
            return getattr(real, name)

    class JaxView:
        def __getattr__(self, name):
            return NoExchange() if name == "lax" else getattr(jax, name)
    monkeypatch.setattr(sn, "jax", JaxView())
    out = cells.run(cells.ring64k_tiny())
    assert not out["correct"], out["checks"]
