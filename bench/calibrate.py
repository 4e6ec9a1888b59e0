"""Readings for a cell's correctness limit, from one process.

  python3 bench/calibrate.py --workload <cell> --seconds <s> \\
      --seeds 11 12 13 --control-seeds 11 12 13

For each of ``--seeds`` it serves the cell's traffic for one window,
samples the finished requests as a benchmark run does, and prints the
widest gap of the program's served tokens below the float32 reference
(the number a run compares), the reference-side control (the float8
pass's choice read at the same positions), and the count of served
state leaves not in the configuration's type.  ``--control-seeds`` does
the same with the program's own lower-precision path switched on (its
int8 KV cache).  The stage shapes compile as they come (no warm-up).
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import registry, run as R  # noqa: E402


def readings(cfg, block, cell, seed, reqs, picked) -> dict:
    """The served tokens' widest gap below the block's float32
    reference, and its float8 control's at the same positions."""
    w = block.make(cfg, seed)
    served, ctrl, n = [], [], 0
    for r in picked:
        prompt = reqs[r["i"]]["prompt"]
        g = block.served_gaps(w, cfg, prompt, r["tokens"])
        served.append(float(g.max()))
        n += len(g)
        ctrl.append(float(block.control_gaps(
            w, cfg, prompt, r["tokens"]).max()))
    del w
    return {"requests": len(picked), "tokens": n,
            "gap_max": max(served, default=None), "gap_per_request": served,
            "fp8_control_gap_max": max(ctrl, default=None),
            "fp8_control_per_request": ctrl}


def one(cfg, block, cell, mix, seed, seconds, kv_quant: bool) -> dict:
    server, eng, _ = R.build(cfg, block, seed, kv_quant=kv_quant,
                             warm=False)
    reqs = R.plan_requests(cell, mix, cfg, seconds, seed)
    ctx = R.drive(server, eng, cell, reqs, seconds)
    state = R.state_below_dtype(eng, cfg["dtype"])
    R.stop(server, eng)
    del server, eng
    gc.collect()
    picked = R.sample(ctx["records"], cell["check"], seed)
    out = {"seed": seed, "kv_quant": kv_quant,
           "state_not_" + cfg["dtype"]: state}
    out.update(readings(cfg, block, cell, seed, reqs, picked))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    spec = registry.benchmark()
    wl = registry.workload(args.workload, spec)
    R.compile_cache()
    R.chip(wl)
    cell = registry.cell(args.workload)
    cfg = registry.config(wl["config"])
    block = registry.block(cfg["block"])
    mix = registry.traffic(wl["traffic"])
    for kv_quant, seeds in ((False, args.seeds), (True, args.control_seeds)):
        for seed in seeds:
            print(json.dumps(one(cfg, block, cell, mix, seed,
                                 args.seconds, kv_quant)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
