"""Faults planted in the decode step the runtime compiles, for showing that
``correct`` comes out false: a token altered where it is produced, a step
that returns its state unchanged, tokens handed to the wrong slots. The
CPU tests plant them in tiny cells; ``limits.py --fault`` plants one at a
cell's own size on the chip. The benchmark's own runs never plant one."""
from __future__ import annotations

import jax.numpy as jnp


def _token_altered(orig):
    def f(prog, params, cache, tokens):
        tok, new = orig(prog, params, cache, tokens)
        return (tok + 1) % prog.cfg.vocab_size, new
    return f


def _state_unchanged(orig):
    def f(prog, params, cache, tokens):
        tok, _ = orig(prog, params, cache, tokens)
        return tok, cache
    return f


def _slots_swapped(orig):
    def f(prog, params, cache, tokens):
        tok, new = orig(prog, params, cache, tokens)
        return jnp.roll(tok, 1, axis=0), new
    return f


FAULTS = {"sound": None, "token_altered": _token_altered,
          "state_unchanged": _state_unchanged,
          "slots_swapped": _slots_swapped}


def planted(name: str):
    """The decode step with fault ``name`` in it, or None for "sound";
    it replaces ``repro.core.runtime.lm_decode_sample``."""
    from repro.core import runtime
    wrap = FAULTS[name]
    return None if wrap is None else wrap(runtime.lm_decode_sample)
