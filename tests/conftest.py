"""Shared test config.

hypothesis is a dev-extra (requirements-dev.txt); a fresh checkout without
it must not fail collection (the seed repo died with ModuleNotFoundError
before running a single test).  Modules that use hypothesis fall back to
these stubs, which skip ONLY the property tests — every example-based test
in the same module still runs.  CI installs hypothesis, so nothing is
skipped there.
"""
import pytest

try:
    import hypothesis  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def given(*_a, **_k):
    """Stand-in for hypothesis.given: replaces the test with a skip."""
    def deco(_f):
        def _skipper():
            pytest.skip("hypothesis not installed (see requirements-dev.txt)")
        _skipper.__name__ = _f.__name__
        _skipper.__doc__ = _f.__doc__
        return _skipper
    return deco


def settings(*_a, **_k):
    """Stand-in for hypothesis.settings: identity decorator."""
    return lambda f: f


class _Strategies:
    """Stand-in for hypothesis.strategies: any strategy constructor resolves
    to an inert placeholder (never drawn from — the test is skipped)."""

    def __getattr__(self, name):
        return lambda *a, **k: None


st = _Strategies()


@pytest.fixture
def chip_selection(monkeypatch):
    """The kernel choice a TPU makes, on this host: the factory's backend
    probe answers "TPU", so every ``"auto"`` op takes its TPU impl, and
    the Pallas kernels run interpreted, as there is no chip to compile
    for.  ``REPRO_KERNEL_IMPL`` is cleared so the table decides."""
    from repro.kernels import factory, ops
    monkeypatch.delenv(factory.IMPL_ENV, raising=False)
    monkeypatch.setattr(factory, "_ON_TPU", True)
    monkeypatch.setattr(ops, "_interpret", lambda: True)
