"""A Pallas kernel's share of its roofline, from the trace.

The least time of one call is the largest of

  bytes in HBM          / HBM bandwidth
  bytes read from VMEM  / VMEM read bandwidth
   + bytes written to VMEM / VMEM write bandwidth
  operations            / peak FLOP/s

where the bytes are those of the call's operands and results, read from the
op's own HLO text in the trace (the compiled chunk program's instruction),
each counted in the memory space its layout names. The share is the summed
least time over the summed measured time of the calls in the window, in %.
"""
from __future__ import annotations

from chipbench import trace
from chipbench.peaks import VMEM_SPACE


def call_bytes(op) -> tuple[dict, dict]:
    """(operand bytes, result bytes) of one call, by memory space."""
    _, shape, _, rest = trace.split_instruction(op.text)
    return (trace.shape_bytes(trace.operand_text(rest)),
            trace.shape_bytes(shape))


def operand_shapes(op) -> list:
    """[(dims...)] of the call's array operands, in order."""
    _, _, _, rest = trace.split_instruction(op.text)
    out = []
    for _, dims, _ in trace._SHAPE.findall(trace.operand_text(rest)):
        out.append(tuple(int(d) for d in dims.split(",") if d))
    return out


def least_seconds(op, peaks: dict, flops: float) -> tuple[float, str]:
    """(least time of one call, what bounds it)."""
    reads, writes = call_bytes(op)
    hbm = sum(v for k, v in reads.items() if k != VMEM_SPACE) \
        + sum(v for k, v in writes.items() if k != VMEM_SPACE)
    times = {
        "hbm": hbm / peaks["hbm_bytes"],
        "vmem": reads.get(VMEM_SPACE, 0) / peaks["vmem_read_bytes"]
        + writes.get(VMEM_SPACE, 0) / peaks["vmem_write_bytes"],
        "flops": flops / peaks["flops"],
    }
    bound = max(times, key=times.get)
    return times[bound], bound


def share(r: trace.Reduction, cell: dict, kernel: str, flops_of) -> float | None:
    """% of the roofline over the window's calls of ``kernel``; None when the
    window holds none. ``flops_of(op)`` counts one call's operations."""
    least = spent = 0.0
    for dev in r.devices:
        for op in trace.chunk_ops(dev, kinds=("kernel",)):
            if op.base == kernel:
                least += least_seconds(op, cell["peaks"], flops_of(op))[0]
                spent += op.dur / 1e9
    if spent <= 0:
        return None
    return 100.0 * least / spent
