"""Optional-``hypothesis`` shim for the property tests.

Prefers the real ``hypothesis`` package when installed. When it is absent
(it is an optional test dependency), falls back to a tiny
deterministic property-test driver implementing the subset of the API
these tests use — ``@given`` over ``integers`` / ``booleans`` /
``sampled_from`` / ``lists`` strategies with ``@settings(max_examples=,
deadline=)``. The fallback draws examples from a seeded PRNG (stable
across runs — failures are reproducible), with no shrinking.

Usage in test modules::

    from _hypothesis_fallback import given, settings, strategies as st
"""

from __future__ import annotations

try:
    from hypothesis import given, settings, strategies  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


    import random
    from types import SimpleNamespace

    _DEFAULT_MAX_EXAMPLES = 20

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def example(self, rng: random.Random):
            return self._draw(rng)

    def _integers(min_value=None, max_value=None):
        lo = -(2 ** 31) if min_value is None else min_value
        hi = 2 ** 31 - 1 if max_value is None else max_value
        return _Strategy(lambda rng: rng.randint(lo, hi))

    def _booleans():
        return _Strategy(lambda rng: rng.random() < 0.5)

    def _sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda rng: elements[rng.randrange(len(elements))])

    def _floats(min_value=0.0, max_value=1.0, **_):
        return _Strategy(lambda rng: rng.uniform(min_value, max_value))

    def _lists(elem, min_size=0, max_size=10):
        def draw(rng):
            size = rng.randint(min_size, max_size)
            return [elem.example(rng) for _ in range(size)]
        return _Strategy(draw)

    strategies = SimpleNamespace(integers=_integers, booleans=_booleans,
                                 sampled_from=_sampled_from, floats=_floats,
                                 lists=_lists)

    def given(*strats, **kw_strats):
        def decorate(fn):
            # NOTE: no functools.wraps — the wrapper must present a
            # ZERO-argument signature or pytest treats the property's
            # parameters as fixtures.
            def wrapper():
                n = getattr(wrapper, "_max_examples", _DEFAULT_MAX_EXAMPLES)
                # Seed from the test name so every test gets a distinct but
                # run-to-run stable example stream (hash() of str is salted
                # per process; use a stable digest instead).
                base = int.from_bytes(
                    fn.__qualname__.encode(), "little") % (2 ** 31)
                for i in range(n):
                    rng = random.Random(base + i * 7919)
                    drawn = [s.example(rng) for s in strats]
                    kw = {k: s.example(rng) for k, s in kw_strats.items()}
                    try:
                        fn(*drawn, **kw)
                    except Exception as e:
                        raise AssertionError(
                            f"property falsified on example {i}: "
                            f"args={drawn} kwargs={kw}") from e
            wrapper.__name__ = fn.__name__
            wrapper.__qualname__ = fn.__qualname__
            wrapper.__doc__ = fn.__doc__
            wrapper.__module__ = fn.__module__
            wrapper.hypothesis = SimpleNamespace(inner_test=fn)
            return wrapper
        return decorate

    def settings(max_examples: int = _DEFAULT_MAX_EXAMPLES, **_ignored):
        """Accepts and ignores options the fallback has no use for
        (deadline, suppress_health_check, ...)."""
        def decorate(fn):
            fn._max_examples = max_examples
            return fn
        return decorate
