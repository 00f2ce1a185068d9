"""Faults planted in the timed path, to show that the comparison catches
them. Each wraps a function of the program for as long as the context
lasts; the program's files are not touched.

* ``unchanged``: the step returns its state as it came in (only the step
  counter moves);
* ``half_batch``: every gradient oracle sees the first half of its
  sequence's positions, and the loss is the mean over those;
* ``no_gossip``: every gossip round is left out (each node keeps its own
  x and h);
* ``no_link_drop``: the channel loses no link (the gossip weights are the
  topology's, unrepaired), in a cell whose traffic has a lossy channel.
"""

from __future__ import annotations

import contextlib
import dataclasses

FAULTS = ("unchanged", "half_batch", "no_gossip", "no_link_drop")


def applies(name: str, traffic: dict) -> bool:
    """Whether the cell's traffic can have fault ``name``."""
    if name == "no_link_drop":
        return traffic.get("channel", {}).get("link_drop", 0) > 0
    return True


def _half(batch):
    tok = batch["tokens"]
    return {**batch, "tokens": tok[:, :tok.shape[1] // 2]}


def model_wrap(name):
    """The model wrapper the harness applies for ``name`` (or None)."""
    if name != "half_batch":
        return None
    return lambda model: model._replace(
        train_loss=lambda p, b: model.train_loss(p, _half(b)))


@contextlib.contextmanager
def planted(name):
    """Plant fault ``name`` in the timed path while the context lasts."""
    from repro.core import engine
    from repro.exp import registry

    if name == "no_link_drop":
        orig_channels = registry.build_channel_models
        registry.build_channel_models = lambda s, seed=0: orig_channels(
            dataclasses.replace(s, link_drop=0.0), seed)
        try:
            yield
        finally:
            registry.build_channel_models = orig_channels
        return
    orig = engine.step
    if name == "unchanged":
        def step(rule, state, ops, obs=()):
            new, aux = orig(rule, state, ops, obs)
            return state._replace(k=new.k), aux
    elif name == "no_gossip":
        def step(rule, state, ops, obs=()):
            return orig(rule, state,
                        ops._replace(mix=lambda off, r, tree: tree), obs)
    elif name == "half_batch":
        step = orig
    else:
        raise ValueError(f"unknown fault {name!r} (have {FAULTS})")
    engine.step = step
    try:
        yield
    finally:
        engine.step = orig
