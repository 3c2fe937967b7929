"""round_stats_roofline (%): the `round_stats` Pallas kernel's share of its
roofline (see chipbench/roofline.py). Layer: kernels
(`kernels/round_fused.py`). Moves samples_per_s.

Operations of one call, from its first operand (theta, (m_pad, n_pad)):
per element the prox (4), the dot with x (2), x*x (2), the nonzero count
(1), the column sum of w (1) and the w_bar dot (3): 13 m_pad n_pad, all on
the vector unit, so they never bound it against the matrix unit's peak."""
from chipbench import roofline, trace

KERNEL = "round_stats"


def flops(op) -> float:
    m_pad, n_pad = roofline.operand_shapes(op)[0]
    return 13.0 * m_pad * n_pad


def read(r: trace.Reduction, cell: dict) -> float | None:
    return roofline.share(r, cell, KERNEL, flops)
