#!/usr/bin/env python3
"""Readings that the limits in ``limits/`` are set from. Run on the chip, in
one process that owns it:

    python3 benchmarks/cells/calibrate.py --workload <name> --seeds 12

For each seed: the program's first steps as a run's set-up makes them (the
same ``Cell``), the reference's, and the gaps between them (the lower
readings). For the first ``--controls`` seeds also the control (the program
with its int8 matmul path switched on) and the planted fault (the reference
put in the program's place on half of the batch, the mean taken over that
half), each against the sound reference (the upper readings). A state left
unchanged reads 1 on ``change_norm_gap`` by the measure and needs no run.
Training's readings need no measured window, so none is made.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--table", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2200000001)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import arch
    import run
    import train

    bench = run.load_json(args.table)
    cell, config = run.find_cell(bench, args.workload)
    config_path = os.path.join(ROOT, config["file"])
    cfg = run.load_json(config_path)
    architecture = arch.find(cfg, config_path,
                             os.path.join(ROOT, cell["base"]))
    traffic = run.load_json(os.path.join(
        ROOT, cell["base"], "traffic", cell["traffic"] + ".json"))
    import jax

    import reference

    model = arch.load(architecture, "reference")

    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    batch, seq = traffic["global_batch"], traffic["seq"]
    opt = train.optimizer_settings(cfg)
    how = run.load_json(os.path.join(
        ROOT, cell["base"], "limits", cell["name"] + ".json"))["reference"]
    offload, steps = how["offload_moments"], how["steps"]
    devices = jax.devices()[:cell["chips"]]

    def program(seed, control=""):
        opts = argparse.Namespace(seed=seed, chips=cell["chips"],
                                  control=control, rehearsal=args.rehearsal,
                                  architecture=architecture)
        c = train.Cell(opts, cfg, traffic)
        got = c.first_steps(steps)
        c.free()
        return got

    def gaps(got, ref):
        checks = reference.compare(got, ref, {})["checks"]
        return {k: v["value"] for k, v in checks.items()}

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.time()
        got = program(seed)
        t1 = time.time()
        ref = reference.follow(model, cfg, opt, seed, batch, seq,
                               steps, offload_moments=offload,
                               devices=devices)
        t2 = time.time()
        row = {"seed": seed, "sound": gaps(got, ref),
               "program_s": round(t1 - t0, 1), "reference_s": round(t2 - t1, 1),
               "losses": got["losses"], "ref_losses": ref["losses"]}
        for k in ("grad_norms", "change_norms"):   # which leaf is worst
            worst = max(range(len(ref[k])), key=lambda j: abs(
                got[k][j] - ref[k][j]) / max(ref[k][j], 1e-30))
            row["worst_" + k] = [worst, got[k][worst], ref[k][worst]]
        if i < args.controls:
            row["control_int8"] = gaps(program(seed, "int8"), ref)
            half = dict(reference.follow(
                model, cfg, opt, seed, batch, seq, steps,
                offload_moments=offload, devices=devices, half=True),
                feed_mismatch=0)
            row["fault_half_batch"] = gaps(half, ref)
        print("calibrate", json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
