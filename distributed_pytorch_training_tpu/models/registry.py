"""Model registry: name -> constructor (the `build_model` factory surface,
/root/reference/train_ddp.py:153-156, generalized to the BASELINE config
matrix)."""

from __future__ import annotations

from typing import Callable, Dict

_REGISTRY: Dict[str, Callable] = {}


def register_model(name: str):
    def deco(fn: Callable):
        _REGISTRY[name] = fn
        return fn

    return deco


def get_model(name: str, **kwargs):
    """Instantiate a registered model (e.g. ``get_model("resnet18",
    num_classes=10)`` ≙ ref :154)."""
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def list_models():
    return sorted(_REGISTRY)


def is_lm_model(name: str) -> bool:
    """The image-vs-token dispatch that the experiment drivers, the trainer
    builders and the serving CLI all ask of a model's name: answered by the
    model it names (one that has a vocabulary), not by the name."""
    return hasattr(get_model(name), "vocab_size")


def lm_vocab(name: str, **overrides) -> int:
    """The vocabulary a token model draws its ids from, as built with
    ``overrides`` (a chip's share of a model holds a slice of it)."""
    return int(get_model(name, **overrides).vocab_size)
