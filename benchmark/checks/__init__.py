"""Per family, what `correct` asks beside the whole model's loss and logits:
``benchmark.checks.<family>.layer_checks(config, traffic, seed)`` returns
``{name: reading}``, and `drivers/train_lm.py` holds each reading
``<what>_diff`` to the configuration's ``correct["<what>_tol"]``. A family without a module here
has no such check."""
