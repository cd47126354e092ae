"""Window positions forwarded per token committed, over everything the traced
run served: the scheduler's ``serving_block_positions_forwarded`` over its
``serving_block_tokens_committed``. (T + 1) * B / B = 5.0 by construction at
B = T = 4 (four denoise forwards and the commit's), more by what a request's
cut last block and a prompt's remainder waste: the price of the mechanism,
and the number a fused commit or fewer steps would move."""


from benchmark.layer_metrics._sdar_regions import counted


def read(run):
    committed = counted(run, "serving_block_tokens_committed")
    if not committed:
        return None
    return counted(run, "serving_block_positions_forwarded") / committed
