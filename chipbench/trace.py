"""Reduction of a profiler trace of the window to what the metric readers read.

`jax.profiler.ProfileData` reads the ``.xplane.pb`` the profiler writes. On a
TPU each chip is a plane ``/device:TPU:<i>`` with the lines ``XLA Modules``
(one event per program execution, named ``<jit name>(<fingerprint>)``) and
``XLA Ops`` (one event per HLO instruction executed, named by the
instruction's own HLO text, ``%round_stats.9 = (...) custom-call(...)``); the
host is the plane ``/host:CPU``, whose lines are threads and whose events
include the benchmark's own `jax.profiler.TraceAnnotation`s. Device and host
events share one clock (nanoseconds from the start of the trace).

The window is the host annotation ``chipbench.window``. Only what lies in it
is kept. Each op is classified from its HLO text:

  kernel      a Pallas kernel (``custom_call_target="tpu_custom_call"``),
              named by its instruction name without the numeric suffix;
  collective  all-reduce, all-gather, collective-permute, ... (sync or the
              ``-start``/``-done`` halves);
  container   while, conditional, call: spans that hold other ops;
  xla         everything else.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "chipbench.window"
CHUNK_PROGRAM = "jit_chunk_fn"        # `repro.api.run`'s jitted chunk program
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute", "all-to-all",
               "reduce-scatter", "collective-broadcast", "send", "recv")
CONTAINERS = ("while", "conditional", "call")
DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
               "u16": 2}
# Host spans that an idle gap of the device is laid to, in this order: the
# harness's own annotations, then what the runtime itself records.
HOST_CATEGORIES = (
    ("stream chunk()", ("chipbench.stream",)),
    ("on_chunk", ("chipbench.on_chunk",)),
    ("program dispatch", ("PJRT_LoadedExecutable_Execute",)),
    ("device-to-host copy", ("D2H Dispatch", "tpu::System::TransferFromDevice")),
)
UNANNOTATED = "runner, unannotated"

_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\](\{[^}]*\})?")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str      # HLO instruction name, e.g. "round_stats.9"
    base: str      # without the numeric suffix, e.g. "round_stats"
    opcode: str    # "custom-call", "fusion", "while", ...
    kind: str      # "kernel" | "collective" | "container" | "xla"
    start: float   # ns
    end: float     # ns
    text: str      # the instruction's HLO text

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    modules: list      # [(program name, start, end)], sorted
    ops: list          # [Op], sorted by start
    program: str = CHUNK_PROGRAM    # the timed program (the driver's)


@dataclasses.dataclass
class Reduction:
    window: tuple      # (start, end) in ns
    devices: list      # [Device]
    host: list         # [(name, start, end)] host events in the window

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


# -- HLO text ----------------------------------------------------------------

def split_instruction(text: str) -> tuple[str, str, str, str]:
    """(name, result shape text, opcode, rest) of one HLO instruction."""
    m = re.match(r"\s*%?(\S+) = ", text)
    if not m:
        return text.strip(), "", "", ""
    name, rest = m.group(1), text[m.end():]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    op = re.match(r"\s*([\w-]+)\(", rest)
    return name, shape, (op.group(1) if op else ""), rest


def shape_bytes(text: str) -> dict:
    """Bytes of every array shape in ``text``, by memory space (0 is HBM;
    the layout's ``S(n)`` names another)."""
    out: dict = {}
    for dtype, dims, layout in _SHAPE.findall(text):
        if dtype not in DTYPE_BYTES:
            continue
        size = DTYPE_BYTES[dtype]
        for d in filter(None, dims.split(",")):
            size *= int(d)
        space = re.search(r"S\((\d+)\)", layout or "")
        key = int(space.group(1)) if space else 0
        out[key] = out.get(key, 0) + size
    return out


def operand_text(rest: str) -> str:
    """The operand list of an instruction (``rest`` from split_instruction)."""
    start = rest.find("(")
    depth = 0
    for i in range(start, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            return rest[start + 1:i]
    return rest[start + 1:]


def classify(text: str) -> tuple[str, str, str, str]:
    """(name, base, opcode, kind) of an op event's HLO text."""
    name, _, opcode, _ = split_instruction(text)
    base = re.sub(r"(\.\d+)+(\.clone)*$", "", name)
    if opcode == "custom-call" and "tpu_custom_call" in text:
        kind = "kernel"
    elif any(opcode.startswith(c) or base.startswith(c) for c in COLLECTIVES):
        kind = "collective"
    elif opcode in CONTAINERS:
        kind = "container"
    else:
        kind = "xla"
    return name, base, opcode, kind


# -- intervals ---------------------------------------------------------------

def union(intervals) -> list:
    """Sorted, merged [(start, end)]."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs, ys) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys) -> list:
    """xs minus ys, both merged."""
    out = []
    for a, b in xs:
        cur = a
        for c, d in ys:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


# -- reading -----------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_trace(path: str, program: str = CHUNK_PROGRAM) -> Reduction:
    """Read one .xplane.pb and keep what lies in the window annotation;
    ``program`` is the name of the timed program (`chunk_spans`)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, window = [], None
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                if e.name == WINDOW:
                    window = span[1:]
                host.append(span)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    lo, hi = window
    host = sorted(h for h in host if h[2] > lo and h[1] < hi
                  and h[0] != WINDOW)
    devices = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    if a >= lo and b <= hi:
                        modules.append((e.name.split("(")[0], a, b))
            elif line.name == "XLA Ops":
                for e in line.events:
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    if a >= lo and b <= hi:
                        name, base, opcode, kind = classify(e.name)
                        ops.append(Op(name, base, opcode, kind, a, b, e.name))
        devices.append(Device(plane.name, sorted(modules, key=lambda m: m[1]),
                              sorted(ops, key=lambda o: o.start), program))
    if not devices:
        raise ValueError("the trace has no /device:TPU plane")
    return Reduction(window=window, devices=devices, host=host)


# -- what the readers share --------------------------------------------------

def chunk_spans(dev: Device) -> list:
    """Merged spans of the timed program's executions on one device."""
    return union((a, b) for name, a, b in dev.modules
                 if name == dev.program)


def chunk_ops(dev: Device, kinds=None) -> list:
    """Ops inside the chunk program's executions (optionally of ``kinds``)."""
    spans = chunk_spans(dev)
    out, j = [], 0
    for op in dev.ops:
        while j < len(spans) and spans[j][1] < op.start:
            j += 1
        if j < len(spans) and spans[j][0] <= op.start and op.end <= spans[j][1]:
            if kinds is None or op.kind in kinds:
                out.append(op)
    return out


def busy(dev: Device) -> list:
    """Merged intervals in which some op ran on the device."""
    return union((op.start, op.end) for op in dev.ops)


def rounds_traced(r: Reduction, chunk_rounds: int) -> int:
    """Rounds whose chunk program ran wholly inside the window (the fewest
    over the devices)."""
    return min(len(chunk_spans(d)) for d in r.devices) * chunk_rounds


def idle_by_host(r: Reduction) -> dict:
    """Seconds the devices sat idle in the window (mean over devices), laid to
    what the host was doing then (HOST_CATEGORIES, first match wins)."""
    cats = [(label, union((a, b) for name, a, b in r.host if name in names))
            for label, names in HOST_CATEGORIES]
    out: dict = {}
    for dev in r.devices:
        rest = subtract([r.window], busy(dev))
        for label, spans in cats:
            out[label] = out.get(label, 0.0) + length(intersect(rest, spans))
            rest = subtract(rest, spans)
        out[UNANNOTATED] = out.get(UNANNOTATED, 0.0) + length(rest)
    return {k: v / len(r.devices) / 1e9 for k, v in out.items()}


def top_ops(r: Reduction, count: int = 10) -> list:
    """[[op name, seconds]] of the ops that took the most device time in the
    window (mean over devices; containers left out)."""
    tot: dict = {}
    for dev in r.devices:
        for op in dev.ops:
            if op.kind != "container":
                tot[op.base] = tot.get(op.base, 0.0) + op.dur
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:count]
    return [[name, ns / len(r.devices) / 1e9] for name, ns in ranked]


def breakdown(r: Reduction) -> dict:
    gaps = sorted(idle_by_host(r).items(), key=lambda kv: -kv[1])
    return {"device_ops": top_ops(r),
            "idle_gaps": [[k, v] for k, v in gaps if v > 0][:10]}


def busy_seconds(r: Reduction) -> float:
    """Mean over the devices of the seconds in which an op ran."""
    return sum(length(busy(d)) for d in r.devices) / len(r.devices) / 1e9
