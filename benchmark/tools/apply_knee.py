"""Write a sweep's result into the mix it was swept for:
``python -m benchmark.tools.apply_knee <knee.json> <traffic/mix.json>`` sets
``rate_rps`` to 0.8 x the knee and keeps the whole sweep (rates tried, backlog
and attainment at each, the rule, date, commit, device) beside it under
``knee``. Only a `benchmark` PR may do this to a mix that is already there."""

from __future__ import annotations

import json
import sys

LOAD_SHARE = 0.8


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    knee = json.loads(open(argv[0]).read())
    if not knee.get("knee_rps"):
        print("apply_knee: the sweep found no rate that sustains",
              file=sys.stderr)
        return 1
    mix = json.loads(open(argv[1]).read())
    mix["rate_rps"] = round(LOAD_SHARE * knee["knee_rps"], 3)
    mix["knee"] = knee
    with open(argv[1], "w") as fh:
        json.dump(mix, fh, indent=2)
        fh.write("\n")
    print(f"rate_rps = {mix['rate_rps']} (0.8 x {knee['knee_rps']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
