"""Share of the steps' top-k assignments (tokens x top-k x layers) that
landed on the experts held here: the program's ``moe_held_assignments``
counter over what the driver says a step assigns. 100 x held / experts under
balanced routing."""


def read(run):
    per_step = run.facts.get("moe_assignments_per_step")
    seen = [e for e in run.events if e.get("kind") == "counter"
            and e.get("name") == "moe_held_assignments"]
    steps = sum(int(e["steps"]) for e in seen)
    if not per_step or not steps:
        return None
    return 100.0 * sum(float(e["value"]) for e in seen) / (steps * per_step)
