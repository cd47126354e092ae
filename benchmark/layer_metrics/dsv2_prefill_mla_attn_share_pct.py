"""Share of a prefill program's device time under `mla_attn` (the expanded
attention: the flash forward kernel and what surrounds it), from the medians
of the regions over the prefills traced."""

from benchmark.layer_metrics import _dsv2_regions, _regions


def read(run):
    got = _regions.region_ms(run, *_dsv2_regions.DSV2_PREFILL)
    if got is None:
        return None
    total = sum(got.values())
    attn = sum(got[r] for r in _dsv2_regions.ATTN)
    return 100.0 * attn / total if total > 0 and attn > 0 else None
