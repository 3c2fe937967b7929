"""repro.obs: spans, metrics registry, event streams, cost loop, and the
telemetry-off bit-identity contract across the runner / sweep / serve stack.
"""
import json
import os
import threading

import numpy as np
import pytest

import repro.obs as obs
from repro.api import ExecConfig, RunSpec, run, run_batch
from repro.launch.obs import main as obs_main
from repro.launch.obs import summarize_events
from repro.obs import (EventLog, MetricsRegistry, Telemetry, Tracer,
                       group_runs, read_events)
from repro.obs.cost import CostModel, analyze_chunk

FIELDS = ("final_w", "loss", "correct", "w_bar_loss", "sparsity",
          "eps_ledger")


def _spec(horizon=8, **kw):
    base = dict(nodes=2, dim=8, horizon=horizon, eps=1.0, alpha0=0.5,
                lam=0.01, stream="drift", stream_options={"period": 3})
    base.update(kw)
    return RunSpec(**base)


@pytest.fixture(autouse=True)
def _ambient_off():
    """Every test starts and ends with the ambient default (disabled)."""
    obs.disable()
    yield
    obs.disable()


# -- tracer ------------------------------------------------------------------

def test_span_nesting_records_parent_and_depth():
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner", k=1):
            pass
    inner, outer = tr.spans
    assert (inner.name, inner.parent, inner.depth) == ("inner", "outer", 1)
    assert (outer.name, outer.parent, outer.depth) == ("outer", None, 0)
    assert inner.args == {"k": 1}
    assert outer.duration_s >= inner.duration_s >= 0.0


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("never") as sp:
        pass
    assert tr.spans == [] and sp.duration_s == 0.0


def test_tracer_thread_stacks_are_independent():
    tr = Tracer()
    # barrier keeps all workers alive at once — thread idents are reused
    # after exit, and the distinct-thread assertion needs real overlap
    gate = threading.Barrier(4)

    def worker(name):
        with tr.span(name):
            gate.wait(timeout=10)

    threads = [threading.Thread(target=worker, args=(f"t{i}",))
               for i in range(4)]
    with tr.span("main"):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    by_name = {s.name: s for s in tr.spans}
    # worker spans ran inside the main span's wall-time but on other
    # threads, so they must NOT pick up "main" as a parent
    assert all(by_name[f"t{i}"].parent is None for i in range(4))
    assert len({s.thread for s in tr.spans}) == 5


def test_tracer_max_spans_drops_not_grows():
    tr = Tracer(max_spans=2)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.spans) == 2 and tr.dropped == 3


def test_chrome_export_shape(tmp_path):
    tr = Tracer()
    with tr.span("phase", engine="sim"):
        pass
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    payload = json.load(open(path))
    events = payload["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(metas) == 1 and len(xs) == 1
    assert xs[0]["name"] == "phase" and xs[0]["args"]["engine"] == "sim"
    assert xs[0]["dur"] >= 0


# -- metrics registry --------------------------------------------------------

def test_registry_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("a").inc(2)
    reg.counter("a").inc(3)
    reg.gauge("g").set(1.5)
    for v in (0.1, 0.2):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["a"] == 5 and snap["g"] == 1.5
    assert snap["h"]["count"] == 2 and abs(snap["h"]["mean"] - 0.15) < 1e-12
    assert reg.names() == ["a", "g", "h"]


def test_registry_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already a Counter"):
        reg.gauge("x")


def test_counter_rejects_negative():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)


def test_counter_concurrent_increments_lose_nothing():
    reg = MetricsRegistry()
    c = reg.counter("n")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


def test_histogram_reservoir_caps_samples():
    reg = MetricsRegistry()
    h = reg.histogram("h", max_samples=10)
    for i in range(100):
        h.observe(float(i))
    assert h.count == 100           # exact count survives the cap
    assert len(h._samples) == 10
    assert h.summary()["max"] == 99.0


# -- event streams -----------------------------------------------------------

def test_event_log_roundtrip_and_grouping(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.emit("run_start", run_id="r1", engine="sim")
    log.emit("chunk", run_id="r1", round_end=4)
    log.emit("publish", round=4)            # no run_id
    log.close()
    events = read_events(path)
    assert [e["event"] for e in events] == ["run_start", "chunk", "publish"]
    runs = group_runs(events)
    assert len(runs["r1"]) == 2 and len(runs[""]) == 1


def test_read_events_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with open(path, "w") as f:
        f.write('{"ts": 1, "event": "a"}\n{"ts": 2, "ev')
    assert [e["event"] for e in read_events(path)] == ["a"]


def test_read_events_raises_on_mid_file_corruption(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with open(path, "w") as f:
        f.write('{"ts": 1, "ev\n{"ts": 2, "event": "b"}\n')
    with pytest.raises(json.JSONDecodeError):
        read_events(path)


# -- Telemetry / ambient -----------------------------------------------------

def test_ambient_default_disabled_and_enable_disable():
    assert obs.active().enabled is False
    tel = obs.enable()
    assert obs.active() is tel and tel.enabled
    obs.disable()
    assert obs.active().enabled is False


def test_disabled_telemetry_is_inert(tmp_path):
    tel = Telemetry(enabled=False, events=str(tmp_path / "e.jsonl"),
                    cost=True)
    with tel.span("x"):
        tel.emit("never")
    assert tel.events is None and tel.cost_enabled is False
    assert tel.tracer.spans == []
    assert not os.path.exists(tmp_path / "e.jsonl")


# -- cost loop ---------------------------------------------------------------

def test_analyze_chunk_predicts_from_hlo():
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda x: x @ x)
    x = jnp.ones((32, 32), jnp.float32)
    model = CostModel(peak_flops=1e12, peak_bandwidth=1e11)
    cc = analyze_chunk(fn, x, model=model)
    assert cc.cost.flops >= 2 * 32 ** 3
    assert cc.predicted_s == model.predict_seconds(cc.cost) > 0
    cc.record(cc.predicted_s)               # measured == predicted
    assert abs(cc.summary()["error_ratio"] - 1.0) < 1e-9
    assert cc.summary()["measured_chunks"] == 1


# -- runner integration ------------------------------------------------------

def _assert_identical(a, b):
    for f in FIELDS:
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f


@pytest.mark.parametrize("engine", ["sim", "dist"])
def test_run_bit_identical_with_telemetry(engine, tmp_path):
    spec = _spec()
    off = run(spec, engine=engine, chunk_rounds=4, warmup=False)
    tel = Telemetry(events=str(tmp_path / "e.jsonl"), cost=True)
    on = run(spec, engine=engine, chunk_rounds=4, warmup=False, obs=tel)
    tel.close()
    _assert_identical(off, on)
    info = on.metrics["obs"]
    assert len(info["run_id"]) == 8
    cost = info["cost"]
    assert cost["measured_chunks"] == 2 and cost["predicted_s"] > 0
    assert cost["error_ratio"] is not None
    kinds = [e["event"] for e in read_events(str(tmp_path / "e.jsonl"))]
    assert kinds == ["run_start", "chunk", "chunk", "chunk_cost", "run_end"]
    assert tel.tracer.summary()["run.chunk"]["count"] == 2
    assert tel.metrics.snapshot()["run.rounds"] == 8
    assert "obs" not in off.metrics         # telemetry off leaves no trace


def test_run_batch_bit_identical_with_telemetry(tmp_path):
    spec = _spec()
    off = run_batch(spec, [0, 1], chunk_rounds=4, warmup=False)
    tel = Telemetry(events=str(tmp_path / "e.jsonl"), cost=True)
    on = run_batch(spec, [0, 1], chunk_rounds=4, warmup=False, obs=tel)
    tel.close()
    for o, n in zip(off, on):
        _assert_identical(o, n)
    # one run_id shared by the whole batch
    ids = {r.metrics["obs"]["run_id"] for r in on}
    assert len(ids) == 1
    events = read_events(str(tmp_path / "e.jsonl"))
    starts = [e for e in events if e["event"] == "run_start"]
    assert starts[0]["kind"] == "run_batch" and starts[0]["seeds"] == [0, 1]
    assert tel.metrics.snapshot()["run_batch.rounds"] == 8


def test_run_checkpoint_events_and_span(tmp_path):
    spec = _spec()
    tel = Telemetry(events=str(tmp_path / "e.jsonl"))
    run(spec, chunk_rounds=4, warmup=False, checkpoint_every=4,
        checkpoint_dir=str(tmp_path / "ckpt"), obs=tel)
    tel.close()
    kinds = [e["event"] for e in read_events(str(tmp_path / "e.jsonl"))]
    assert kinds.count("checkpoint") == 2
    assert tel.tracer.summary()["run.checkpoint"]["count"] == 2


def test_ambient_telemetry_reaches_run():
    tel = obs.enable()
    res = run(_spec(), chunk_rounds=4, warmup=False)
    assert res.metrics["obs"]["run_id"]
    assert tel.metrics.snapshot()["run.rounds"] == 8


# -- profiler annotations ----------------------------------------------------

# the chunk-loop phases of each driver, in loop order (run() with a log,
# checkpoints and an on_chunk hook; run_batch has neither log nor hook)
PHASES = {
    "run": ("stream", "chunk", "dispatch", "wait", "account", "fetch",
            "log", "checkpoint", "on_chunk"),
    "run_batch": ("stream", "chunk", "dispatch", "wait", "account", "fetch",
                  "checkpoint"),
}


def _profiled(fn, trace_dir, prefixes):
    """Call ``fn`` under ``jax.profiler.trace`` and return its result and
    the ``/host:CPU`` events of the trace whose names start with one of
    ``prefixes``, as (name, start_ns, end_ns, stats), ordered by start (an
    enclosing event before the events it holds)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(trace_dir)):
        out = fn()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith(prefixes)]
    return out, sorted(events, key=lambda e: (e[1], -e[2]))


def _drive(driver, tmp_path, tel=None):
    """3 chunks of ``driver`` with every optional phase switched on."""
    cfg = ExecConfig(chunk_rounds=4, warmup=False, checkpoint_every=4,
                     checkpoint_dir=str(tmp_path / "ckpt"), obs=tel)
    if driver == "run":
        return run(_spec(horizon=12),
                   exec=cfg.replace(log_path=str(tmp_path / "log.csv")),
                   on_chunk=lambda *_: False)
    return run_batch(_spec(horizon=12), [0, 1], exec=cfg)


def _loop_events(events, driver):
    """The chunk-loop phase events of ``driver`` (its regret pass left
    out)."""
    names = {f"{driver}.{p}" for p in PHASES[driver]}
    return [e for e in events if e[0] in names]


def _assert_phases(loop, driver):
    assert [e[0] for e in loop] == [f"{driver}.{p}"
                                    for p in PHASES[driver]] * 3
    chunks = [e for e in loop if e[0] == f"{driver}.chunk"]
    for inner in ("dispatch", "wait"):
        held = [e for e in loop if e[0] == f"{driver}.{inner}"]
        assert all(c[1] <= h[1] and h[2] <= c[2]
                   for c, h in zip(chunks, held)), inner
    # the phases follow one another and do not overlap
    top = [e for e in loop if e[0] not in
           {f"{driver}.dispatch", f"{driver}.wait"}]
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))


def test_span_writes_profiler_annotation(tmp_path):
    on, off = Tracer(), Tracer(enabled=False)

    def spans():
        with on.span("phase", engine="sim"):
            pass
        with off.span("quiet", engine="sim") as sp:
            pass
        return sp

    sp, events = _profiled(spans, tmp_path, ("phase", "quiet"))
    by_name = {e[0]: e for e in events}
    # bare names; an enabled span's attributes arrive as stats, a disabled
    # one writes none and keeps nothing in memory
    assert by_name["phase"][3] == {"engine": "sim"}
    assert by_name["quiet"][3] == {}
    assert [s.name for s in on.spans] == ["phase"]
    assert off.spans == [] and sp.duration_s == 0.0


@pytest.mark.parametrize("driver", ["run", "run_batch"])
def test_chunk_loop_phases_on_profiler_clock(driver, tmp_path):
    tel = Telemetry(events=str(tmp_path / "e.jsonl"))
    _, events = _profiled(lambda: _drive(driver, tmp_path, tel),
                          tmp_path / "trace", driver)
    tel.close()
    _assert_phases(_loop_events(events, driver), driver)
    summary = tel.tracer.summary()
    assert all(summary[f"{driver}.{p}"]["count"] == 3
               for p in PHASES[driver])
    chunk = [e for e in events if e[0] == f"{driver}.chunk"]
    assert [e[3]["round_end"] for e in chunk] == [4, 8, 12]


@pytest.mark.parametrize("driver", ["run", "run_batch"])
def test_chunk_loop_phases_with_telemetry_off(driver, tmp_path):
    tel = obs.active()
    assert not tel.enabled
    _, events = _profiled(lambda: _drive(driver, tmp_path),
                          tmp_path / "trace", driver)
    loop = _loop_events(events, driver)
    _assert_phases(loop, driver)
    # the off path passes no attributes and keeps nothing
    assert all(e[3] == {} for e in loop)
    assert tel.tracer.spans == [] and tel.events is None


@pytest.mark.parametrize("driver", ["run", "run_batch"])
def test_profiler_on_bit_identical(driver, tmp_path):
    off = _drive(driver, tmp_path / "off")
    on, events = _profiled(lambda: _drive(driver, tmp_path / "on"),
                           tmp_path / "trace", driver)
    assert _loop_events(events, driver)
    if driver == "run":
        off, on = [off], [on]
    for o, n in zip(off, on):
        _assert_identical(o, n)


# -- sweep integration -------------------------------------------------------

def test_sweep_emits_point_spans_and_events(tmp_path):
    from repro.sweep import SweepSpec, sweep
    tel = obs.enable(events=str(tmp_path / "e.jsonl"))
    sw = SweepSpec(base=_spec(horizon=6), axes={"eps": (0.5, 1.0)},
                   seeds=(0,), name="obs_demo", chunk_rounds=6,
                   compute_regret=False)
    sweep(sw, store=str(tmp_path / "store"), warmup=False)
    assert tel.tracer.summary()["sweep.point"]["count"] == 2
    assert tel.metrics.snapshot()["sweep.points_ran"] == 2
    points = [e for e in read_events(str(tmp_path / "e.jsonl"))
              if e["event"] == "sweep_point"]
    assert len(points) == 2 and all(p["source"] == "ran" for p in points)


# -- serve integration -------------------------------------------------------

def test_serve_counters_spans_and_summary_event(tmp_path):
    from repro.serve import ServeConfig, ServeService
    tel = obs.enable(events=str(tmp_path / "e.jsonl"))
    spec = RunSpec(nodes=2, dim=8, horizon=8, eps=1.0, alpha0=0.5, lam=0.01,
                   stream="bursty")
    svc = ServeService(ServeConfig(spec=spec, chunk_rounds=4, max_batch=4,
                                   max_wait_ms=0.5, warmup=False)).start()
    r = svc.predict([1.0] * 8, node=0, timeout=30.0)
    assert r.status == "ok"
    svc.stop()
    snap = tel.metrics.snapshot()
    assert snap["serve.served"] >= 1 and snap["serve.batches"] >= 1
    assert snap["serve.latency_s"]["count"] >= 1
    assert snap["serve.published"] >= 1
    assert tel.tracer.summary()["serve.batch"]["count"] >= 1
    assert tel.tracer.summary()["serve.publish"]["count"] >= 1
    events = read_events(str(tmp_path / "e.jsonl"))
    summaries = [e for e in events if e["event"] == "serve_summary"]
    assert len(summaries) == 1
    # the exit record carries the FULL admission summary, shed_reasons
    # included — the obs report CLI renders it after the service is gone
    adm = summaries[0]["admission"]
    assert adm["served"] >= 1 and "shed_reasons" in adm
    assert any(e["event"] == "publish" for e in events)


def test_serve_stats_summary_pins_shed_reasons():
    from repro.serve.admission import ServeStats
    stats = ServeStats()
    stats.record_shed(reason="full")
    stats.record_shed(2, reason="timeout")
    out = stats.summary()
    assert out["shed_reasons"] == {"full": 1, "timeout": 2}
    assert out["shed"] == 3


def test_shed_reasons_mirror_into_registry():
    from repro.serve.admission import ServeStats
    tel = obs.enable()
    stats = ServeStats()
    stats.record_shed(reason="timeout")
    stats.record_refused(2)
    snap = tel.metrics.snapshot()
    assert snap["serve.shed.timeout"] == 1 and snap["serve.refused"] == 2


# -- report CLI --------------------------------------------------------------

def test_report_cli_text_and_json(tmp_path, capsys):
    path = str(tmp_path / "e.jsonl")
    tel = Telemetry(events=path, cost=True)
    run(_spec(), chunk_rounds=4, warmup=False, obs=tel)
    tel.close()
    rid = next(iter(summarize_events(path)["runs"]))

    assert obs_main(["report", "--events", path]) == 0
    text = capsys.readouterr().out
    assert f"run {rid}" in text and "cost: predicted" in text

    assert obs_main(["report", "--events", path, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["runs"][rid]["chunks"] == 2
    assert payload["runs"][rid]["cost"]["error_ratio"] is not None

    assert obs_main(["report", "--events", path, "--run", rid]) == 0
    capsys.readouterr()
    assert obs_main(["report", "--events", path, "--run", "nope"]) == 1


def test_report_cli_missing_stream(tmp_path, capsys):
    assert obs_main(["report", "--events",
                     str(tmp_path / "absent.jsonl")]) == 1
    assert "no events" in capsys.readouterr().out
