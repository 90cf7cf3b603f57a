"""Shared test configuration.

``hypothesis`` is an optional dev dependency (see requirements-dev.txt).
When it is not installed we install a minimal stand-in module so the seven
property-based test modules still *collect*; every ``@given`` test then
skips at runtime instead of failing the whole collection with
``ModuleNotFoundError``.
"""
import sys
import types

import pytest


def _install_hypothesis_shim() -> None:
    class _Strategies(types.ModuleType):
        """Any strategy constructor (integers, floats, ...) -> None stub."""

        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _Strategies("hypothesis.strategies")

    def given(*_args, **_kwargs):
        def deco(fn):
            def skipper(*a, **k):
                pytest.skip("hypothesis not installed (see requirements-dev.txt)")

            skipper.__name__ = getattr(fn, "__name__", "hypothesis_test")
            skipper.__doc__ = getattr(fn, "__doc__", None)
            return skipper

        return deco

    def settings(*_args, **_kwargs):
        # used both as @settings(...) decorator and settings(...) object
        def deco(fn):
            return fn

        return deco

    def example(*_args, **_kwargs):
        # a fixed example of a test the shim skips anyway
        return lambda fn: fn

    hyp = types.ModuleType("hypothesis")
    hyp.given = given
    hyp.settings = settings
    hyp.example = example
    hyp.strategies = st
    sys.modules["hypothesis"] = hyp
    sys.modules["hypothesis.strategies"] = st


try:
    import hypothesis  # noqa: F401
except ImportError:
    _install_hypothesis_shim()
