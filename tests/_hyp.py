"""Hypothesis compatibility shim for environments without `hypothesis`.

The container this repo targets does not ship `hypothesis`, and a bare
`from hypothesis import ...` used to crash the WHOLE pytest collection with
a ModuleNotFoundError.  Importing from this module instead gives you:

  * the real `given` / `settings` / strategies when hypothesis is installed
    (install via requirements-dev.txt for full shrinking/fuzzing power);
  * otherwise a minimal deterministic fallback that runs each property test
    over a fixed-seed sample of the declared strategy space.

Only the tiny strategy surface this repo uses is emulated: `integers`,
`floats`, `lists`, `sampled_from`, keyword-style `@given`, and `@settings`
with `max_examples` / `deadline`.
"""
from __future__ import annotations

try:  # real hypothesis if available
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # deterministic stand-in
    import functools
    import random

    HAVE_HYPOTHESIS = False
    _DEFAULT_EXAMPLES = 10

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def draw(self, rnd: random.Random):
            return self._draw(rnd)

    class st:  # noqa: N801 - mimics `hypothesis.strategies` module
        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(lambda r: r.randint(min_value, max_value))

        @staticmethod
        def floats(min_value, max_value):
            return _Strategy(lambda r: r.uniform(min_value, max_value))

        @staticmethod
        def lists(elements, min_size=0, max_size=8):
            return _Strategy(lambda r: [
                elements.draw(r)
                for _ in range(r.randint(min_size, max_size))])

        @staticmethod
        def sampled_from(elements):
            seq = list(elements)
            return _Strategy(lambda r: seq[r.randrange(len(seq))])

    def given(**strats):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args):
                n = getattr(wrapper, "_max_examples",
                            getattr(fn, "_max_examples", _DEFAULT_EXAMPLES))
                rnd = random.Random(0xC0DEDFED)  # fixed seed: reproducible
                for _ in range(n):
                    kwargs = {k: s.draw(rnd) for k, s in strats.items()}
                    fn(*args, **kwargs)
            # pytest must not see the wrapped signature, or it would treat
            # the strategy parameters as fixtures
            del wrapper.__wrapped__
            wrapper._hypothesis_fallback = True
            return wrapper
        return deco

    def settings(max_examples: int = _DEFAULT_EXAMPLES, **_ignored):
        def deco(fn):
            fn._max_examples = max_examples
            return fn
        return deco
