"""Programs set-up compiled because the persistent compile cache did not hold
them: 0 on every run of a cell after its first in a checkout."""


def read(run):
    misses = run.facts.get("cache_misses_setup")
    return None if misses is None else float(misses)
