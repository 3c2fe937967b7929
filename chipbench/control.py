"""The readings that the limits of `correct` are set from, on the chip, for
a cell of the linear learner (`chipbench/drivers/gossip_linear.py`).

    python3 chipbench/control.py --workload <cell> --seeds 100-111 \
        --control-seeds 200-202 [--out readings.jsonl]

In one process, at the cell's own size:

  program  each of ``--seeds``: set-up as a benchmark run makes it (the
           traffic from the seed, `repro.api.run` through the compared
           chunks), then the comparison with the reference; no window.
  control  each of ``--control-seeds``: the reference computed one
           precision lower (the configuration's ``control``) put in the
           program's place, against the reference.
  faults   each of ``--control-seeds``: the reference with one fault
           planted, in the program's place: the state left unchanged, half
           the nodes' samples left out, one answer altered, and on a
           node-sharded cell the exchange between chips left out.

Every reading is one JSON line; the last line is the summary: for each
number, the largest reading of the program (the lower reading) and the
smallest of the control and of each fault.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import compare, harness  # noqa: E402
from chipbench.drivers import gossip_linear as linear  # noqa: E402


def seeds(text: str) -> list:
    out = []
    for part in filter(None, text.split(",")):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b or a) + 1))
    return out


def program_readings(cell: dict, seed: int) -> dict:
    """The compared numbers of the program over the compared chunks: set-up
    as a benchmark run makes it, with no window."""
    drv = linear.Driver(cell, seed)
    drv.prepare()
    count = [0]

    def stop(*args):
        count[0] += 1
        if count[0] == drv.open_at:
            drv.keep(*args)
            return True
        return False
    drv.run(stop)
    values = drv.readings(drv.open_at)
    drv.release()
    return values


def faulty(base, fault: str, chips: int):
    """A reference class with ``fault`` planted in its round."""
    import jax.numpy as jnp

    class Faulty(base):
        def _round(self, carry, batch):
            x, y, follow = batch
            if fault == "half_batch":
                x = x.at[1::2].set(0.0)
            new, out = super()._round(carry, (x, y, follow))
            if fault == "frozen":
                new = (carry[0], new[1], new[2])
            return new, out

        def _mix(self, tilde):
            if fault != "no_exchange":
                return super()._mix(tilde)
            nw = (1.0 - self.self_weight) / 2.0
            blocks = tilde.reshape(chips, -1, tilde.shape[-1])
            mixed = (self.self_weight * blocks
                     + nw * jnp.roll(blocks, 1, axis=1)
                     + nw * jnp.roll(blocks, -1, axis=1))
            return mixed.reshape(tilde.shape)
    return Faulty


def reference_outputs(cell: dict, seed: int, traffic, sharding,
                      precision: str = "highest", fault: str | None = None,
                      follow=None):
    import jax

    cfg = cell["config"]
    refmod = linear.reference_module(cfg["reference"]["module"])
    cls = refmod.Reference if fault is None else \
        faulty(refmod.Reference, fault, cell["chips"])
    ref = cls(cfg, precision=precision, sharding=sharding)
    K = int(cfg["compare_chunks"])
    with jax.default_matmul_precision("highest"):
        out = ref.run(seed, [traffic.chunk_data(k) for k in range(K)],
                      follow=follow)
    out["eps"] = refmod.eps_ledger(cfg["spec"]["eps"],
                                   K * linear.sizes(cfg)[2], traffic.disjoint)
    if fault == "altered":
        out["loss"][-1, 0] += 1e-3
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--no-faults", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(harness.ROOT / "src"))
    device = harness.check_devices(cell["chips"])
    harness.enable_cache()
    cfg = cell["config"]
    nodes, dim, _ = linear.sizes(cfg)
    entries = nodes * dim
    sink = open(args.out, "a") if args.out else None
    rows = []

    def emit(kind, seed, values, secs):
        row = {"workload": args.workload, "kind": kind, "seed": seed,
               "seconds": secs, "device": device["kind"], **values}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        emit("program", seed, program_readings(cell, seed),
             time.perf_counter() - t0)
        gc.collect()

    variants = [("control", cfg["control"], None)]
    if not args.no_faults:
        variants += [(f, "highest", f) for f in ("frozen", "half_batch",
                                                 "altered")]
        if cell["chips"] > 1:
            variants.append(("no_exchange", "highest", "no_exchange"))
    for seed in seeds(args.control_seeds):
        _, _, traffic, sharding = linear.build(cell, seed)
        traffic.prepare()
        for kind, precision, fault in variants:
            t0 = time.perf_counter()
            alt = reference_outputs(cell, seed, traffic, sharding,
                                    precision=precision, fault=fault)
            ref = reference_outputs(cell, seed, traffic, sharding,
                                    follow=alt["loss"])
            emit(kind, seed, compare.readings(alt, ref, entries=entries),
                 time.perf_counter() - t0)
            del alt, ref
        traffic.release()
        gc.collect()

    summary = {"workload": args.workload, "summary": {}}
    for name in compare.NUMBERS:
        per = {}
        for row in rows:
            if row["kind"] == "program":
                per["program_max"] = max(per.get("program_max", 0.0), row[name])
            else:
                key = f"{row['kind']}_min"
                per[key] = min(per.get(key, float("inf")), row[name])
        summary["summary"][name] = per
    print(json.dumps(summary), flush=True)
    if sink:
        sink.write(json.dumps(summary) + "\n")
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
