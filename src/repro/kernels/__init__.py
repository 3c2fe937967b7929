"""Pallas TPU kernels for the paper's per-round hot loop.

The round pipeline (clip -> Laplace-noise -> gossip-mix -> sparse-OMD
update -> L1 prox) is memory-bound at the paper's dimensions; these
kernels fuse it into streamed passes over the (m, n) parameter block (see
`round_fused` and docs/kernels.md). `ops` wraps the seed kernels
(`pdomd_update`, `hinge_grad`) with padding + interpret-mode defaults;
`ref` holds the pure-jnp oracles every kernel is allclose-tested against.

The kernels are reached through `RunSpec(backend="pallas")` — see
`repro.api.backends`; on the CPU they run with ``interpret=True`` so the
tests check the real kernel bodies.
"""
from repro.kernels.round_fused import (DEFAULT_BLOCK_COLS, LANE, SUBLANE,
                                       VMEM_LIMIT_BYTES, col_block, dual_step,
                                       node_sum, round_stats, round_update,
                                       vmem_bytes)

__all__ = ["round_stats", "round_update", "dual_step", "node_sum", "LANE",
           "SUBLANE", "DEFAULT_BLOCK_COLS", "VMEM_LIMIT_BYTES", "col_block",
           "vmem_bytes"]
