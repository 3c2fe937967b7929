"""round_update_roofline (%): the `round_update` Pallas kernel's share of its
roofline (see chipbench/roofline.py). Layer: kernels
(`kernels/round_fused.py`). Moves samples_per_s.

Operations of one call, from its first two operands (A (m_pad, m_pad),
theta (m_pad, n_pad)): the dense mix A @ recv, 2 m_pad^2 n_pad, and per
element the noise add, the self-term correction, the dual step and the
crash select (7): 2 m_pad^2 n_pad + 7 m_pad n_pad."""
from chipbench import roofline, trace

KERNEL = "round_update"


def flops(op) -> float:
    shapes = roofline.operand_shapes(op)
    m_pad, n_pad = shapes[1]
    return 2.0 * m_pad * m_pad * n_pad + 7.0 * m_pad * n_pad


def read(r: trace.Reduction, cell: dict) -> float | None:
    return roofline.share(r, cell, KERNEL, flops)
