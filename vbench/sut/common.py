"""Shared by the builders: the configuration's serving sizes become a
``ServingConfig`` (every other field at its default)."""

from __future__ import annotations

_TUPLES = ("prefill_buckets", "prefill_batch_sizes")


def serving_config(sizes: dict):
    from vtpu.serving import ServingConfig

    kw = {k: (tuple(v) if k in _TUPLES else v) for k, v in sizes.items()}
    return ServingConfig(**kw)


def engine(model, serving):
    from vtpu.serving import ServingEngine

    return ServingEngine(serving=serving, model=model)
