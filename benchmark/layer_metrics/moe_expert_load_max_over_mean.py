"""Mean over the window's print boundaries of the program's
``moe_expert_load_max_over_mean`` gauge: per step the largest held expert's
assignments over the mean held expert's, in the worst layer."""


def read(run):
    values = [float(e["value"]) for e in run.events
              if e.get("kind") == "gauge"
              and e.get("name") == "moe_expert_load_max_over_mean"]
    return sum(values) / len(values) if values else None
