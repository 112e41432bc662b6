"""Readings that set the limits of a cell's check (see PERF.md, "Limits").

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --what program,control

One JSON line per seed and reading, on whatever machine JAX finds (the
readings that set a limit are taken on the chip, at the cell's size). For a
training cell, without a measured window or a save:

* ``program``: the program's checked steps against the plain reference;
* ``control``: the reference in fp8 in the program's place;
* ``half_batch`` / ``state_unchanged``: the program with that fault planted.

For a restore cell, a short run of the cell itself (one restore), and
``control``: the same with every restored float32 leaf passed through
bfloat16.
"""
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402


def training(cell, seed: int, what: str) -> dict:
    from bench.traffic.train_ckpt import Job, compare_training
    from bench.faults import FAULTS
    from bench.harness import Run

    run = Run(cell, seed, 0.0, False, time.monotonic(), FAULTS.get(what))
    job = Job(run, run.fault)
    try:
        if what == "control":
            job.close_trainer()
            prog = job.reference_readings("fp8")
        else:
            prog = job.program_readings()
            job.close_trainer()
        ref = job.reference_readings("f32")
    finally:
        job.close()
        run.close()
    gaps = compare_training(prog, ref, cell["workload"]["limits"]["grad_rule"])
    return dict(gaps, losses=[float(x) for x in prog["losses"]],
                ref_losses=[float(x) for x in ref["losses"]])


def restoring(cell, seed: int, what: str) -> dict:
    from bench.faults import FAULTS
    from bench.harness import run_cell

    fault = FAULTS["bf16_restore"] if what == "control" else None
    dev = {"platform": "", "kind": "", "count": 1}
    out = run_cell(cell, seed, 1.0, False, time.monotonic(), dev, None, fault)
    return {k: v["value"] for k, v in out["compared"].items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program")
    args = ap.parse_args()

    from bench import files
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    cell = files.resolve(args.workload)
    read = training if cell["entry"]["traffic"] == "train_ckpt" else restoring
    for what in args.what.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.monotonic()
            out = read(cell, seed, what)
            print(json.dumps(dict(cell=args.workload, what=what, seed=seed,
                                  secs=time.monotonic() - t, **out)), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
