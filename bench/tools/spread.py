#!/usr/bin/env python3
"""Spread of each end-to-end metric over two sets of runs of one cell.

    python bench/tools/spread.py OUT_DIR/sets_otto.train.jsonl

Reads lines ``{"set": "A", "seed": 1, "result": <run.py's last line>}``
and prints, per metric, each set's median and its spread (the distance
between the first and third quartile of ``statistics.quantiles(values,
n=4)``, as a share of the median), the wider of the two, five times it
(the bound it would give) and the second set's median against the first.
"""
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    sets = {}
    for line in open(path):
        rec = json.loads(line)
        res = rec["result"]
        assert res["correct"], rec
        for name, m in res["metrics"].items():
            sets.setdefault(name, {}).setdefault(rec["set"], []).append(
                m["value"])
    for name, by_set in sorted(sets.items()):
        rows = {s: (statistics.median(v), spread(v), len(v))
                for s, v in sorted(by_set.items())}
        widest = max(r[1] for r in rows.values())
        meds = [r[0] for r in rows.values()]
        print(json.dumps({"metric": name, "sets": rows, "widest": widest,
                          "five_x": 5 * widest,
                          "median_shift": meds[-1] / meds[0] - 1}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
