"""device_idle_share (%): the share of the traced window in which no op ran
on the device, 1 - busy / window, averaged over the chips of the cell.
Layer: device. Moves samples_per_s."""
from chipbench import trace


def read(r: trace.Reduction, cell: dict) -> float | None:
    return 100.0 * (1.0 - trace.busy_seconds(r) * 1e9 / r.window_ns)
