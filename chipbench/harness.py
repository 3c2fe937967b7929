"""One run of one benchmark cell: set-up, the measured window, the check.

A cell (an entry of ``workloads`` in BENCHMARK.json) names a configuration
(``chipbench/configs/<name>.json``) and a traffic mix
(``chipbench/traffic/<name>.json``); its per-layer metrics are read by
``chipbench/metrics/<name>.py``, and the configuration's ``driver`` (by
default ``gossip_linear``) is ``chipbench/drivers/<name>.py``. Everything is
found by name, so a new cell, mix, metric or driver is new files and new
entries, and no edit here.

The driver runs the system under test and says what one operation is
(`chipbench.drivers`); the harness keeps what every cell shares:

  set-up   the driver's ``prepare`` (the traffic), then its ``run`` up to the
           ``open_at``-th operation (the operations the reference follows);
  window   opens at that operation's hook and closes at the first hook at
           least ``--seconds`` later, which stops the run; the end-to-end
           numbers come from the window's marks on the harness's own clock;
  check    once the window has closed and the peak memory is read, the
           driver's ``readings`` (the program's state freed, then the plain
           reference) and `chipbench.compare.judge` against the limits.

With ``--trace 1`` the profiler records the first second or so of the
window (whole operations) and the per-layer metrics are read from that
trace, with the driver's timed program and ``info``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import resource
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".chipbench" / "trace"
TRACE_SECONDS = 1.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- finding things by name --------------------------------------------------

DEFAULT_DRIVER = "gossip_linear"


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def driver_file(name: str, root: Path = ROOT) -> Path:
    """``chipbench/drivers/<name>.py``; a name with no such file is refused."""
    path = root / "chipbench" / "drivers" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no driver {name!r}: {path.relative_to(root)} "
                         "does not exist")
    return path


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of BENCHMARK.json, with its configuration, traffic
    mix and the metrics it reports."""
    bench = _json(root / "BENCHMARK.json")
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    wl = found[0]

    def applies(metric):
        return name in metric.get("workloads", [name])

    config = _json(root / "chipbench" / "configs" / f"{wl['config']}.json")
    return {
        "name": name,
        "chips": int(wl["chips"]),
        "config": config,
        "traffic": _json(root / "chipbench" / "traffic" / f"{wl['traffic']}.json"),
        "driver": str(driver_file(config.get("driver", DEFAULT_DRIVER), root)),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def driver(cell: dict):
    """The ``Driver`` class of the cell's driver file."""
    path = Path(cell["driver"])
    return _module(path, f"chipbench.drivers.{path.stem}").Driver


def reader(metric: str, root: Path = ROOT):
    """The ``read(reduction, cell)`` function of one per-layer metric."""
    path = root / "chipbench" / "metrics" / f"{metric}.py"
    return _module(path, f"chipbench.metrics.{metric.replace('.', '_')}").read


# -- the machine -------------------------------------------------------------

def check_devices(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache(root: Path = ROOT) -> str:
    """JAX's persistent compile cache: $JAX_COMPILATION_CACHE_DIR if set,
    else the fixed directory .jax_cache/ in the checkout. Every program is
    kept, so only a checkout's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def host_steal_s() -> float:
    """Seconds of CPU time the hypervisor gave to other guests, summed over
    this host's cores (/proc/stat); 0.0 where the file is not there."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return 0.0


def usage() -> tuple:
    """This process's (minor faults, major faults, involuntary context
    switches, CPU seconds) so far, over all its threads."""
    u = resource.getrusage(resource.RUSAGE_SELF)
    return (u.ru_minflt, u.ru_majflt, u.ru_nivcsw, u.ru_utime + u.ru_stime)


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


# -- the window --------------------------------------------------------------

class Window:
    """The hook the driver calls after every operation: has the driver keep
    the compared state, opens and closes the window, and starts and stops
    the trace."""

    def __init__(self, seconds: float, open_at: int, trace: bool, keep):
        self.seconds, self.open_at, self.trace = seconds, open_at, trace
        self.keep = keep            # the driver's, called at the open
        self.k = 0
        self.t_open = self.t_close = None
        self.marks: list = []       # perf_counter at each chunk in the window
        self.usage: list = []       # usage() at the window's open and marks
        self._ann = None
        self.compiles = 0
        self.gc_pauses: list = []   # (generation, seconds) inside the window
        self._gc0 = None

    def on_gc(self, phase, info):
        if phase == "start":
            self._gc0 = time.perf_counter()
        elif self.t_open is not None and self.t_close is None \
                and self._gc0 is not None:
            self.gc_pauses.append((info["generation"],
                                   time.perf_counter() - self._gc0))

    def on_compile(self, event, duration, **_):
        if event == COMPILE_EVENT and self.t_open is not None \
                and self.t_close is None:
            self.compiles += 1

    def __call__(self, *args) -> bool:
        import jax

        now = time.perf_counter()
        self.k += 1
        with jax.profiler.TraceAnnotation("chipbench.on_chunk"):
            if self.k == self.open_at:
                self.keep(*args)
                if self.trace:
                    self._start_trace()
                self.steal = host_steal_s()
                self.usage.append(usage())
                self.t_open = time.perf_counter()
                return False
            if self.t_open is None:
                return False
            self.marks.append(now)
            self.usage.append(usage())
            if self._ann is not None and \
                    now - self._t_trace >= TRACE_SECONDS:
                self._stop_trace()
            if now - self.t_open >= self.seconds:
                if self._ann is not None:
                    self._stop_trace()
                self.t_close = now
                self.steal = host_steal_s() - self.steal
                return True
        return False

    def _start_trace(self):
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("chipbench.window")
        self._ann.__enter__()
        self._t_trace = time.perf_counter()

    def _stop_trace(self):
        import jax

        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, t0: float,
             device: dict) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax

    from chipbench import compare

    phases = {"start": time.perf_counter() - t0}
    drv = driver(cell)(cell, seed)
    drv.prepare()
    phases["traffic"] = time.perf_counter() - t0
    window = Window(seconds, open_at=drv.open_at, trace=trace, keep=drv.keep)
    jax.monitoring.register_event_duration_secs_listener(window.on_compile)
    gc.callbacks.append(window.on_gc)
    try:
        drv.run(window)
    finally:
        gc.callbacks.remove(window.on_gc)
    if window.t_close is None:
        raise RuntimeError("the run ended before the window closed")
    out_device = dict(device, memory_peak_bytes=memory_peak(cell["chips"]))

    # the window's numbers, on the harness's own clock
    span = window.t_close - window.t_open
    marks = [window.t_open] + window.marks
    chunk_s = [b - a for a, b in zip(marks, marks[1:])]
    e2e = {"samples_per_s": drv.samples_per_op * len(window.marks) / span,
           "chunk_p95_ms": 1e3 * _percentile(chunk_s, 95),
           "setup_s": window.t_open - t0}

    values = drv.readings(window.k)
    correct, checks = compare.judge(values, cell["config"]["limits"])
    drv.release()

    result = {"correct": correct, "attempted": len(window.marks),
              "failed": 0}
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
        result["device"] = out_device
    else:
        metrics, extra, bd = read_trace(cell, device["kind"], drv)
        result["metrics"] = metrics
        result["device"] = dict(out_device, **extra)
        result["breakdown"] = bd
    result["checks"] = checks
    phases["window"] = window.t_open - t0
    order = sorted(range(len(chunk_s)), key=lambda i: -chunk_s[i])
    longest, middle = order[:5], order[len(order) // 2]
    result["_window"] = {"chunks": len(window.marks), "seconds": span,
                         "compiles": window.compiles, "setup": phases,
                         "median_chunk_s": statistics.median(chunk_s),
                         "steal_s": window.steal,
                         "gc": (len(window.gc_pauses),
                                max((p for _, p in window.gc_pauses),
                                    default=0.0)),
                         "longest": [(i, chunk_s[i], _delta(window.usage, i))
                                     for i in longest],
                         "median_usage": _delta(window.usage, middle)}
    return result


def read_trace(cell: dict, kind: str, drv) -> tuple[dict, dict, dict]:
    """(per-layer metrics, busy_s/window_s, breakdown) from the window's
    trace, with the driver's timed program and reader ``info``."""
    from chipbench import peaks, trace

    r = trace.reduce_trace(trace.find_xplane(str(TRACE_DIR)), drv.program)
    info = dict(drv.info, chips=cell["chips"], peaks=peaks.peaks(kind),
                rounds=trace.rounds_traced(r, drv.info["chunk_rounds"]))
    metrics = {}
    for m in cell["per_layer"]:
        value = reader(m["name"])(r, info)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    extra = {"busy_s": trace.busy_seconds(r), "window_s": r.window_ns / 1e9}
    return metrics, extra, trace.breakdown(r)


def _delta(marks: list, i: int) -> tuple:
    """usage() over chunk i of the window, CPU seconds rounded to ms."""
    d = [b - a for a, b in zip(marks[i], marks[i + 1])]
    return (*d[:3], round(d[3], 3))


def _percentile(xs, q: float) -> float:
    """The q-th percentile, linear between closest ranks (numpy's default)."""
    xs = sorted(xs)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def report(result: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, then the result as the last line of standard output."""
    info = result.pop("_window", None)
    if info:
        print(f"set-up (s from start): {json.dumps(info['setup'])}",
              file=sys.stderr)
        print(f"window: {info['chunks']} chunks in {info['seconds']:.3f} s, "
              f"{info['compiles']} compiles inside it; median chunk "
              f"{info['median_chunk_s']:.6f} s, usage {info['median_usage']}; "
              f"longest (index, s, usage: (minor "
              f"faults, major faults, involuntary switches, CPU s)) "
              f"{info['longest']}; gc pauses (count, longest s) "
              f"{info['gc']}; host steal {info['steal_s']:.2f} s",
              file=sys.stderr)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv: list, t0: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="chipbench/run.py",
                                 description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        device = check_devices(cell["chips"])
    except NoChip as err:
        print(f"chipbench: {err}", file=sys.stderr)
        return 3
    enable_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0,
                      device)
    report(result)
    return 0
