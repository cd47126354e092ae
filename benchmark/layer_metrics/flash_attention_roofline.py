"""The flash attention kernels' share of their roofline: the least time the
chip could take for the kernel calls of one train step (per call the larger
of FLOPs over the bf16 peak and bytes over the HBM peak, from the shapes, by
benchmark/flops) over the device time of the Pallas calls in one executed
step, median over the steps traced. Causal attention at S=1024, D=64 has
~340 FLOPs per byte, so the FLOP bound applies."""

from benchmark import stats
from benchmark.flops import gpt2 as gpt2_flops
from benchmark.run import WINDOW_MARK
from benchmark.trace_reduce import is_pallas_call


def read(run):
    trace, shape = run.trace_data, run.facts.get("train_shape")
    if trace is None or not trace.devices or run.peaks is None \
            or not shape or shape["attention"] != "flash":
        return None
    window = trace.window(WINDOW_MARK)
    per_step = []
    for lanes in trace.devices.values():
        kernels = [e for e in lanes.ops if is_pallas_call(e.name)]
        for m in lanes.modules:
            if "train_step" in m.name and m.start_ns >= window[0] \
                    and m.end_ns <= window[1]:
                per_step.append(sum(
                    e.dur_ns for e in kernels
                    if e.start_ns >= m.start_ns and e.end_ns <= m.end_ns)
                    / 1e9)
    device_s = stats.median([t for t in per_step if t > 0])
    if device_s is None:
        return None
    least = 0.0
    for backward in (False, True):
        cost = gpt2_flops.flash_call_cost(
            shape["batch"], shape["seq_len"], shape["heads"],
            shape["head_dim"], backward)
        least += max(cost["flops"] / (run.peaks["bf16_tflops"] * 1e12),
                     cost["bytes"] / (run.peaks["hbm_gb_per_s"] * 1e9))
    return 100.0 * least * shape["layers"] / device_s
