"""round_mfu (%): the whole round's share of the chip's peak: the least time
of a round over the measured time of a round in the traced window (the
window's length over the rounds whose chunk program ran wholly inside it).
Layer: the whole round. Moves samples_per_s.

The least time of a round on one chip is the larger of its share of the
data, m / chips * n * 4 bytes of x, over the HBM bandwidth (the only bytes
that must cross HBM in a round; theta may stay in VMEM), and its
algorithmic operations, about 16 m n / chips (prox 4, margin 2, clip norm
2, noise add 1, ring mix 5, dual step 2), over the peak FLOP/s. Bytes bound
it at every size this benchmark runs."""
from chipbench import trace


def read(r: trace.Reduction, cell: dict) -> float | None:
    rounds = cell["rounds"]
    if rounds <= 0:
        return None
    per_chip = cell["m"] * cell["n"] / cell["chips"]
    peaks = cell["peaks"]
    least = max(per_chip * 4 / peaks["hbm_bytes"],
                16.0 * per_chip / peaks["flops"])
    measured = r.window_ns / 1e9 / rounds
    return 100.0 * least / measured
