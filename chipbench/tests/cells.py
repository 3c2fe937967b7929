"""Small copies of the benchmark's cells, for tests on the CPU."""
import copy
import time

from chipbench import harness

SEED = 2**31 + 11


def tiny_cell(name: str, spec: dict, exec_: dict) -> dict:
    """The cell ``name`` of BENCHMARK.json at a size the CPU can run, with
    keys of its configuration's ``spec`` and ``exec`` replaced."""
    cell = copy.deepcopy(harness.load_cell(name))
    cell["config"]["spec"].update(spec)
    cell["config"]["exec"].update(exec_)
    return cell


def sec5_tiny() -> dict:
    return tiny_cell("sec5.replay", {"nodes": 16, "dim": 384},
                     {"chunk_rounds": 8})


def sec5_stream_tiny() -> dict:
    return tiny_cell("sec5.stream", {"nodes": 16, "dim": 384},
                     {"chunk_rounds": 8})


def ring64k_tiny() -> dict:
    return tiny_cell("ring64k.sharded4", {"nodes": 64, "dim": 256},
                     {"chunk_rounds": 2})


def run(cell: dict, seed: int = SEED, seconds: float = 0.3) -> dict:
    import jax

    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            device)
