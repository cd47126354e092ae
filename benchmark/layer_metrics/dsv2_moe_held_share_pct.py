"""Share of the decode steps' top-k assignments (slot rows x top-k x expert
layers) that landed on the experts held here: the engine's
``moe_held_assignments`` step counter (kept on the device, fetched once after
the window) over what its counted steps assigned. 100 x held / experts
(12.5) under balanced routing. Every slot row routes, live or not, so the
denominator is the engine's ``rows``."""


def read(run):
    got, shape = run.facts.get("step_counters"), run.facts.get("serve_shape")
    if not got or not shape or not got.get("steps") \
            or not shape.get("moe_assignments_per_token"):
        return None
    return 100.0 * got["moe_held_assignments"] / (
        got["steps"] * shape["rows"] * shape["moe_assignments_per_token"])
