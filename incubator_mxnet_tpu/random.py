"""Global random state over JAX's counter-based threefry PRNG.

The reference keeps per-device Philox/MT generator states inside a
ResourceManager (include/mxnet/random_generator.h, src/resource.cc) and ops
request ``kRandom`` resources.  On TPU the idiomatic design is explicit
functional keys; this module bridges the two worlds:

* Eager mode: a process-global seed + monotonically increasing counter;
  each random op folds the counter into the seed key, so ``mx.random.seed(n)``
  gives reproducible streams (documented contract, docs/migration.md
  "RNG streams differ": keys, ``next_key``'s ``fold_in`` and every sampler
  here are threefry, NOT bitwise-equal to the reference's Philox/MT —
  SURVEY.md §7 "RNG parity").  One consumer of these keys draws its bits
  elsewhere: the op ``Dropout`` hands its threefry key to XLA's
  ``RngBitGenerator`` (``ops/nn_ops.dropout``), so a dropout mask is a
  function of its key on one backend, and NOT equal across backends (a
  TPU and a CPU draw different masks from one key).
* Traced mode (hybridize/CachedOp): the tracer installs a base key that is
  an *input* to the compiled program via ``key_scope``; random ops split
  from it deterministically, keeping compiled graphs pure.
"""
from __future__ import annotations

import threading

import jax

from .locks import named_lock

__all__ = ["seed", "next_key", "key_scope", "uniform", "normal", "randint",
           "current_seed"]

_state = threading.local()
_global = {"seed": 0, "counter": 0}
_lock = named_lock("random.state")


def seed(seed_state: int, ctx=None):  # ctx accepted for API parity
    """Reset the global stream (reference python/mxnet/random.py seed)."""
    with _lock:
        _global["seed"] = int(seed_state)
        _global["counter"] = 0


def current_seed() -> int:
    return _global["seed"]


class key_scope:
    """Install a traced base key: random ops inside derive from it.

    ``key=None`` installs a LAZY default: the base key (PRNGKey(0))
    materializes only if some op actually draws randomness.  A
    deterministic forward then traces zero PRNG equations — graphlint's
    GL-DEAD001 flagged the eager default as dead work in every
    inference graph."""

    def __init__(self, key):
        self.key = key

    def __enter__(self):
        stack = getattr(_state, "keys", None)
        if stack is None:
            stack = _state.keys = []
        stack.append([self.key, 0])
        return self

    def __exit__(self, *exc):
        _state.keys.pop()


def next_key():
    """A fresh PRNG key: traced-scope derived if tracing, else global."""
    stack = getattr(_state, "keys", None)
    if stack:
        entry = stack[-1]
        if entry[0] is None:          # lazy key_scope default
            entry[0] = jax.random.PRNGKey(0)
        entry[1] += 1
        return jax.random.fold_in(entry[0], entry[1])
    with _lock:
        _global["counter"] += 1
        counter = _global["counter"]
        base = _global["seed"]
    return jax.random.fold_in(jax.random.PRNGKey(base), counter)


# Convenience eager samplers (the full op set lives in ndarray.random).
def uniform(low=0.0, high=1.0, shape=(), dtype="float32", ctx=None, out=None):
    from . import ndarray as nd

    return nd.random.uniform(low, high, shape, dtype=dtype, ctx=ctx, out=out)


def normal(loc=0.0, scale=1.0, shape=(), dtype="float32", ctx=None, out=None):
    from . import ndarray as nd

    return nd.random.normal(loc, scale, shape, dtype=dtype, ctx=ctx, out=out)


def randint(low, high=None, shape=(), dtype="int32", ctx=None, out=None):
    from . import ndarray as nd

    return nd.random.randint(low, high, shape, dtype=dtype, ctx=ctx, out=out)
