"""Model FLOP/s utilization: closed-form FLOPs per token (benchmark/flops)
x tokens/s/chip over the chip's bf16 peak, from the steps timed before the
profiler starts. Recomputation is never counted."""

def read(run):
    if run.peaks is None or "tokens_per_s_chip" not in run.facts:
        return None
    return (100.0 * run.facts["flops_per_token"]
            * run.facts["tokens_per_s_chip"]
            / (run.peaks["bf16_tflops"] * 1e12))
