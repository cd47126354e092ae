"""Say what a profiler trace holds: ``python -m benchmark.tools.describe_trace
<file.xplane.pb[.gz]> [out.json]``. Planes, lanes, event counts, the events
with most time and a sample of their stats. Look at one trace by hand before
trusting a reduction of it."""

from __future__ import annotations

import json
import sys

from benchmark import trace_reduce


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    text = json.dumps(trace_reduce.describe(argv[0]), indent=1, default=str)
    if len(argv) > 1:
        with open(argv[1], "w") as fh:
            fh.write(text + "\n")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
