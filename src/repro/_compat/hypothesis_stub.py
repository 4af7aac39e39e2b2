"""Minimal property-testing fallback for environments without
``hypothesis``.

``tests/conftest.py`` calls :func:`install` when the real package is
missing (it is a dev dependency — see ``pyproject.toml`` — but some
sandboxes can't install it). The stub registers ``hypothesis`` /
``hypothesis.strategies`` modules implementing the small API surface our
tests use: ``given``, ``settings``, and the ``integers`` / ``booleans`` /
``floats`` / ``sampled_from`` / ``lists`` / ``tuples`` strategies.

``given`` re-runs the test body ``max_examples`` times with values drawn
from a per-test deterministic RNG (seeded by crc32 of the test name), so
runs are reproducible. No shrinking, no database — failures report the
drawn arguments and nothing more.
"""
from __future__ import annotations

import inspect
import random
import sys
import types
import zlib

_DEFAULT_MAX_EXAMPLES = 25


class SearchStrategy:
    def __init__(self, draw):
        self._draw = draw

    def example_from(self, rng: random.Random):
        return self._draw(rng)


def integers(min_value=None, max_value=None) -> SearchStrategy:
    lo = -(2 ** 31) if min_value is None else min_value
    hi = 2 ** 31 if max_value is None else max_value
    return SearchStrategy(lambda rng: rng.randint(lo, hi))


def booleans() -> SearchStrategy:
    return SearchStrategy(lambda rng: rng.random() < 0.5)


def floats(min_value=0.0, max_value=1.0, **_kw) -> SearchStrategy:
    return SearchStrategy(lambda rng: rng.uniform(min_value, max_value))


def sampled_from(elements) -> SearchStrategy:
    elements = list(elements)
    return SearchStrategy(lambda rng: elements[rng.randrange(len(elements))])


def lists(elements: SearchStrategy, min_size=0, max_size=None,
          **_kw) -> SearchStrategy:
    hi = max_size if max_size is not None else min_size + 10

    def draw(rng):
        n = rng.randint(min_size, hi)
        return [elements.example_from(rng) for _ in range(n)]
    return SearchStrategy(draw)


def tuples(*strategies: SearchStrategy) -> SearchStrategy:
    return SearchStrategy(
        lambda rng: tuple(s.example_from(rng) for s in strategies))


def settings(max_examples: int = _DEFAULT_MAX_EXAMPLES, **_kw):
    def decorate(fn):
        fn._stub_max_examples = max_examples
        return fn
    return decorate


def given(*strategies: SearchStrategy):
    def decorate(fn):
        # like hypothesis, positional strategies fill the rightmost
        # parameters; the wrapper's signature shows pytest only the rest
        # (fixtures and parametrize arguments).
        params = list(inspect.signature(fn).parameters.values())
        n_free = len(params) - len(strategies)
        drawn_names = [p.name for p in params[n_free:]]

        def wrapper(*args, **kwargs):
            n = getattr(wrapper, "_stub_max_examples",
                        getattr(fn, "_stub_max_examples",
                                _DEFAULT_MAX_EXAMPLES))
            seed = zlib.crc32(f"{fn.__module__}.{fn.__qualname__}".encode())
            rng = random.Random(seed)
            for _ in range(n):
                drawn = tuple(s.example_from(rng) for s in strategies)
                try:
                    fn(*args, **kwargs, **dict(zip(drawn_names, drawn)))
                except Exception as exc:
                    raise AssertionError(
                        f"{fn.__name__} failed on drawn arguments "
                        f"{drawn!r}: {exc}") from exc
        wrapper.__name__ = fn.__name__
        wrapper.__qualname__ = fn.__qualname__
        wrapper.__doc__ = fn.__doc__
        wrapper.__module__ = fn.__module__
        wrapper.__dict__.update(fn.__dict__)
        wrapper.__signature__ = inspect.Signature(params[:n_free])
        return wrapper
    return decorate


def install() -> None:
    """Register the stub as ``hypothesis`` if the real one is absent."""
    if "hypothesis" in sys.modules:
        return
    mod = types.ModuleType("hypothesis")
    mod.__doc__ = "stub (repro._compat.hypothesis_stub)"
    mod.given = given
    mod.settings = settings
    st = types.ModuleType("hypothesis.strategies")
    for name in ("integers", "booleans", "floats", "sampled_from", "lists",
                 "tuples"):
        setattr(st, name, globals()[name])
    st.SearchStrategy = SearchStrategy
    mod.strategies = st
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st
