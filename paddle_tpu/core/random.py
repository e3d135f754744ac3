"""Global RNG with paddle seed semantics on threaded JAX PRNG keys.

The reference keeps per-device generator state (paddle.seed, Generator;
reference: paddle/phi/core/generator.cc — unverified, SURVEY.md §0). Here a
``Generator`` is a (key, counter) pair: every random op draws
``fold_in(key, counter++)`` so eager calls are sequenced deterministically
after ``paddle.seed`` while each draw stays an independent stream — the
functional-JAX analog of advancing Philox offset state.
"""
from __future__ import annotations

import time

import jax
import numpy as np

__all__ = [
    "Generator",
    "seed",
    "default_generator",
    "next_key",
    "get_rng_state",
    "set_rng_state",
    "RNGStatesTracker",
    "get_rng_state_tracker",
]


class Generator:
    def __init__(self, seed_: int | None = None):
        if seed_ is None:
            seed_ = time.time_ns() % (2**31)
        self.manual_seed(seed_)

    def manual_seed(self, seed_: int):
        self._seed = int(seed_)
        # made on the first draw: building a key initialises the XLA
        # backend, and `import paddle_tpu` (hence the launcher parent)
        # must not take the chip from the process that needs it
        self._key = None
        self._counter = 0
        return self

    def initial_seed(self) -> int:
        return self._seed

    def next_key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(self._seed)
        k = jax.random.fold_in(self._key, self._counter)
        self._counter += 1
        return k

    def get_state(self):
        return (self._seed, self._counter)

    def set_state(self, state):
        seed_, counter = state
        self.manual_seed(seed_)
        self._counter = int(counter)


default_generator = Generator(0)

# While tracing (to_static / jitted train steps), random ops must draw from
# a TRACED key that enters the compiled program as an input — otherwise the
# mask freezes at trace time. ``traced_key_scope`` pushes such a key.
_traced_key_stack: list = []


class traced_key_scope:
    def __init__(self, key):
        self._key = key

    def __enter__(self):
        _traced_key_stack.append([self._key, 0])
        return self

    def __exit__(self, *exc):
        _traced_key_stack.pop()
        return False


def seed(value: int) -> Generator:
    """paddle.seed(v): reseed the global generator (and return it)."""
    return default_generator.manual_seed(value)


def next_key():
    if _traced_key_stack:
        entry = _traced_key_stack[-1]
        k = jax.random.fold_in(entry[0], entry[1])
        entry[1] += 1
        return k
    return default_generator.next_key()


def get_rng_state():
    return default_generator.get_state()


def set_rng_state(state):
    default_generator.set_state(state)


class RNGStatesTracker:
    """Named RNG streams for tensor-parallel dropout.

    Mirrors fleet's get_rng_state_tracker (reference:
    python/paddle/distributed/fleet/layers/mpu/random.py — unverified):
    ``local_seed`` streams differ per model-parallel rank (dropout masks
    differ across mp shards), ``global_seed`` streams agree.
    """

    def __init__(self):
        self._states: dict[str, Generator] = {}

    def add(self, name: str, seed_: int):
        if name in self._states:
            raise ValueError(f"rng state {name} already exists")
        self._states[name] = Generator(seed_)

    def reset(self):
        self._states = {}

    def states(self):
        return {k: g.get_state() for k, g in self._states.items()}

    def set_states(self, states):
        self._states = {}
        for k, s in states.items():
            g = Generator(0)
            g.set_state(s)
            self._states[k] = g

    class _Scope:
        def __init__(self, tracker, name):
            self.tracker, self.name = tracker, name

        def __enter__(self):
            self._saved = default_generator.get_state()
            g = self.tracker._states[self.name]
            default_generator.set_state(g.get_state())
            return self

        def __exit__(self, *exc):
            self.tracker._states[self.name].set_state(
                default_generator.get_state()
            )
            default_generator.set_state(self._saved)
            return False

    def rng_state(self, name: str = "global_seed"):
        if name not in self._states:
            self.add(name, np.random.randint(0, 2**31))
        return RNGStatesTracker._Scope(self, name)


_tracker = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _tracker
