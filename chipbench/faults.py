"""Faults planted under the timed path, for the tests that show the
comparison catches them and for reading each fault's numbers on the chip.
The benchmark's own runs plant none."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


class _HalfBatch:
    """The model with half of every batch left out: the loss is the mean
    over the first half of the rows only."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def loss(self, params, batch):
        half = batch["tokens"].shape[0] // 2
        return self._model.loss(params, {"tokens": batch["tokens"][:half]})


def plant_train(fault, model, opt):
    """Returns (model, opt) with ``fault`` planted, or unchanged."""
    from repro.core.factored import FactoredLeaf
    if fault is None:
        return model, opt
    if fault == "half_batch":
        return _HalfBatch(model), opt
    if fault == "zero_factors":
        # Adapprox's factored second moment lost after every step: Q and U
        # come back zero, as from an S-RSI that returned nothing
        def zeroed(state):
            return jax.tree.map(
                lambda x: dataclasses.replace(
                    x, q=jnp.zeros_like(x.q), u=jnp.zeros_like(x.u))
                if isinstance(x, FactoredLeaf) else x, state,
                is_leaf=lambda x: isinstance(x, FactoredLeaf))

        def update(grads, state, params):
            updates, state = opt.update(grads, state, params)
            return updates, zeroed(state)
        return model, opt._replace(update=update)
    if fault == "frozen_state":
        # a step that returns its state unchanged: no update, no new state
        def update(grads, state, params):
            del params
            return jax.tree.map(jnp.zeros_like, grads), state
        return model, opt._replace(update=update)
    raise ValueError(f"unknown train fault {fault!r}")


def plant_serve(fault, engine, vocab: int) -> None:
    """``altered_token``: at every 8th decode step the token of every busy
    row is replaced by the next id, as the decode program returns it.  A
    request with 8 or more decode tokens (every request of the serve
    cell, and the longest of any sample) carries at least one."""
    if fault is None:
        return
    if fault != "altered_token":
        raise ValueError(f"unknown serve fault {fault!r}")
    decode = engine._decode_jit
    count = {"n": 0}

    def altered(params, pool, tokens, tables, positions):
        toks, pool = decode(params, pool, tokens, tables, positions)
        count["n"] += 1
        if count["n"] % 8 == 0:
            busy = jnp.asarray(tables)[:, 0] != 0   # idle rows: null block
            toks = jnp.where(busy, (toks + 1) % vocab, toks)
        return toks, pool

    engine._decode_jit = altered
