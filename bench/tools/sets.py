#!/usr/bin/env python3
"""Run one cell several times, one process per run, and keep each run's
result line: the sets that `spread.py` reads.

    python3 bench/tools/sets.py OUT_DIR otto.train 10 \
        A:11,12,13,14,15,16 B:11,12,13,14,15,16 T:21,22,23

Each ``TAG:seeds`` group runs ``bench/run.py`` once per seed, with
``--trace 1`` for the tag ``T``; lines ``{"set", "seed", "rc", "s",
"result", "stderr_tail"}`` are appended to ``OUT_DIR/sets_<cell>.jsonl``.
This process never touches JAX, so each run holds the chip alone.
"""
import json
import os
import subprocess
import sys
import time


def main(argv=None) -> int:
    out, cell, secs, *groups = argv or sys.argv[1:]
    os.makedirs(out, exist_ok=True)
    for group in groups:
        tag, seeds = group.split(":")
        for seed in seeds.split(","):
            t = time.time()
            p = subprocess.run(
                ["python3", "bench/run.py", "--workload", cell, "--seed",
                 seed, "--seconds", secs, "--trace",
                 "1" if tag == "T" else "0"],
                capture_output=True, text=True)
            rec = {"set": tag, "seed": int(seed), "rc": p.returncode,
                   "s": round(time.time() - t, 1)}
            try:
                rec["result"] = json.loads(p.stdout.strip().splitlines()[-1])
            except (IndexError, ValueError):
                rec["stdout_tail"] = p.stdout[-2000:]
            rec["stderr_tail"] = p.stderr[-1500:]
            with open(os.path.join(out, f"sets_{cell}.jsonl"), "a") as f:
                f.write(json.dumps(rec) + "\n")
            r = rec.get("result", {})
            print(tag, seed, p.returncode, rec["s"], r.get("correct"),
                  {k: v["value"] for k, v in r.get("metrics", {}).items()},
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
