"""Mean over the block steps of the engine's
``moe_expert_load_max_over_mean`` step counter (kept on the device, fetched
once after the window): per step the busiest expert's assignments over the
mean expert's, in the worst layer. Every slot row's window routes, live or
not."""


def read(run):
    got = run.facts.get("step_counters")
    if not got or not got.get("steps") \
            or "moe_expert_load_max_over_mean" not in got:
        return None
    return got["moe_expert_load_max_over_mean"] / got["steps"]
