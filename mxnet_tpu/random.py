"""Random state management.

The reference seeds per-device mshadow PRNGs (ref: src/common/random_generator.h,
python/mxnet/random.py — mx.random.seed).  TPU-native design: a functional
threaded key.  Eagerly, a global RandomState splits a jax PRNG key per draw.
Inside a trace (hybridize / jit), the tracing machinery pushes a TraceRandomScope
whose key is a traced argument, so compiled graphs are reproducible and pure.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["seed", "next_key", "RandomScope", "KeyTape", "current_key_source"]

_tls = threading.local()


class _EagerState:
    def __init__(self, seed_val: int = 0):
        # the key materializes on FIRST DRAW, not at construction:
        # jax.random.key() initializes the jax backend, and package
        # import must stay backend-free — jax.distributed.initialize
        # (and so the elastic shutdown→re-init round-trip) is only legal
        # before any computation runs
        self._seed = int(seed_val)
        self.key = None

    def next_key(self):
        if self.key is None:
            self.key = jax.random.key(self._seed)
        self.key, sub = jax.random.split(self.key)
        return sub


_GLOBAL = _EagerState()


class RandomScope:
    """Functional key source for traced regions.

    Holds a base key (usually a tracer); each ``next_key`` folds in a counter
    so a traced forward draws deterministic independent streams.
    """

    def __init__(self, base_key):
        self.base_key = base_key
        self._count = 0

    def next_key(self):
        k = jax.random.fold_in(self.base_key, self._count)
        self._count += 1
        return k

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _tls.stack.pop()


class KeyTape(RandomScope):
    """Hands out keys that were drawn earlier, in the order they were drawn.

    A pending Parameter draws its initializer's keys from the framework
    stream when it is initialized and the initializer runs later, alone or
    traced into the one program that materialises a whole model
    (gluon/parameter.py).  Either way it sees these keys, so the stream is
    the one an eager ``initialize()`` would have consumed.
    """

    def __init__(self, keys):
        super().__init__(None)
        self._keys = list(keys)

    def next_key(self):
        if self._count == len(self._keys):
            raise RuntimeError(
                f"initializer drew more than the {len(self._keys)} PRNG "
                f"key(s) its abstract run drew: the number of draws must "
                f"depend on name, shape and dtype alone")
        self._count += 1
        return self._keys[self._count - 1]


def current_key_source():
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1]
    return _GLOBAL


def next_key():
    return current_key_source().next_key()


def seed(seed_state: int, ctx=None):  # ctx accepted for API compat
    """Reseed the global generator (ref: mx.random.seed)."""
    global _GLOBAL
    _GLOBAL = _EagerState(int(seed_state))
