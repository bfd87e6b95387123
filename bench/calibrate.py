#!/usr/bin/env python3
"""Readings that the output check's limit is set from, on the chip.

    python3 bench/calibrate.py --workload qwen3-1.7b.chat --seconds 30 --seeds 1 2 3

For each seed, in one process: a run of the cell as bench/run.py makes it
(set-up, warm-up, window and output check), then, on the same
sample of served requests, the widest gap of the served tokens (the
program's reading) and of the tokens that the reference run in int8 puts
first (the control's reading, bench/reference: the step below the
configuration's bfloat16). One JSON line per seed, then the largest
program reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from run import ROOT, compile_cache, run_cell  # bench/run.py also sets sys.path


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    compile_cache(ROOT)
    import jax
    from bench import check, loader, model
    from repro.engine import factory as F

    _, cfg_entry, _, _ = loader.cell(loader.benchmark(ROOT), args.workload)
    spec = model.load(ROOT / cfg_entry["file"])
    reference = model.reference_module(ROOT, spec)
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        line = run_cell(ROOT, args.workload, seed, args.seconds, False,
                        t_start=t)
        picked = check.sample(line["_records"], model.seed_words(seed))
        weights = model.make_weights(spec, seed, jax.devices()[0])
        g = check.gaps(reference, weights, spec,
                       [(r.prompt, r.tokens) for r in picked],
                       F.MAX_MODEL_LEN, control=True)
        del weights
        prog = np.concatenate([p for p, _ in g])
        ctrl = np.concatenate([c for _, c in g])
        row = {"seed": seed, "program": float(prog.max()),
               "control": float(ctrl.max()),
               "program_mean": float(prog.mean()),
               "control_mean": float(ctrl.mean()),
               "program_flips": float((prog > 0).mean()),
               "control_flips": float((ctrl > 0).mean()),
               "program_p90": float(np.quantile(prog, 0.9)),
               "control_p90": float(np.quantile(ctrl, 0.9)),
               "tokens": sum(len(r.tokens) for r in picked),
               "requests": len(picked),
               "run_gap": line["compared"]["max_logit_gap"]["value"],
               "seconds": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"program_max": max(r["program"] for r in rows),
                      "control_min": min(r["control"] for r in rows),
                      "seeds": len(rows)}), flush=True)


if __name__ == "__main__":
    main()
