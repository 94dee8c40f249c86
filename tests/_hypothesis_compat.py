"""``hypothesis`` compatibility shim.

The container this repo targets does not ship hypothesis, and the PR
rules forbid installing it.  Property tests import ``given/settings/
strategies`` from here: the real library is used when present; otherwise
a minimal deterministic fallback runs each property over a fixed number
of seeded samples (enough to keep the sweeps meaningful, not a full
shrinking engine).
"""
try:  # pragma: no cover - exercised only where hypothesis exists
    from hypothesis import example, given, settings, strategies  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:  # deterministic fallback
    import random

    HAVE_HYPOTHESIS = False

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def example(self, rng):
            return self._draw(rng)

    class _Strategies:
        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(lambda rng: rng.randint(min_value, max_value))

        @staticmethod
        def floats(min_value, max_value):
            return _Strategy(lambda rng: rng.uniform(min_value, max_value))

        @staticmethod
        def sampled_from(options):
            opts = list(options)
            return _Strategy(lambda rng: opts[rng.randrange(len(opts))])

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: bool(rng.getrandbits(1)))

        @staticmethod
        def tuples(*strats):
            return _Strategy(lambda rng: tuple(s.example(rng) for s in strats))

    strategies = _Strategies()

    def settings(max_examples=10, deadline=None, **_kw):
        def deco(fn):
            fn._max_examples = max_examples
            return fn

        return deco

    def example(**kwargs):
        """Pin one explicit case; ``given`` runs pinned cases first."""

        def deco(fn):
            fn._examples = [kwargs] + list(getattr(fn, "_examples", []))
            return fn

        return deco

    def given(**strategy_kwargs):
        def deco(inner):
            # no functools.wraps: pytest must see the zero-arg signature,
            # not the property's drawn parameters
            def runner():
                for pinned in getattr(inner, "_examples", []):
                    inner(**pinned)
                n = getattr(runner, "_max_examples", 10)
                rng = random.Random(0)
                for _ in range(n):
                    drawn = {
                        k: s.example(rng) for k, s in strategy_kwargs.items()
                    }
                    inner(**drawn)

            runner.__name__ = inner.__name__
            runner.__doc__ = inner.__doc__
            runner._max_examples = getattr(inner, "_max_examples", 10)
            return runner

        return deco
