"""Medians and spreads of a set of runs, as the driver reads them:
``python -m benchmark.tools.spreads <run logs...>``. Each log is the stdout of
one ``benchmark.run``; its last JSON line is read. Per metric: the values, the
median, and the spread (distance between the quartiles over the median). A
bound is about five times the widest spread over the cells, never under 1%."""

from __future__ import annotations

import json
import sys
from collections import defaultdict

from benchmark import stats


def last_result(path: str) -> dict:
    lines = [x for x in open(path).read().splitlines() if x.startswith("{")]
    return json.loads(lines[-1])


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    values = defaultdict(list)
    for path in argv:
        line = last_result(path)
        if not line["correct"] or line["failed"]:
            print(f"NOT CORRECT: {path}: {line['failed']} failed")
        for name, m in line["metrics"].items():
            values[name].append(m["value"])
    for name, xs in sorted(values.items()):
        print(json.dumps({"metric": name, "n": len(xs),
                          "median": stats.median(xs),
                          "spread": stats.spread(xs), "values": xs}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
