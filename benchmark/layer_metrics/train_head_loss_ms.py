"""Device time of one train step in the embeddings, the final LayerNorm, the
tied head and the loss over the vocabulary-wide logits (`wte`, `wpe`, `ln_f`,
`head`, `loss`), forward and backward; the fused AdamW epilogue of the token
table's gradient included, and no collective (see `train_attention_ms`)."""

from benchmark.layer_metrics import _regions


def read(run):
    return _regions.read(run, _regions.TRAIN_STEP, ("wte", "wpe", "ln_f", "head", "loss"))
