"""The entry point of every span call site: :func:`span`.

Each layer marks its regions with ``spans.span(name, **attrs)``.  What a
span records is decided by whoever switches tracing on
(:func:`repro.runtime.tracing.start`), which sets the opener here with
:func:`set_opener`.  With no opener set, :func:`span` returns one shared
no-op context manager, so a call site costs one ``is None`` test.

This module imports nothing from ``repro``: the ring and CKKS layers carry
spans without depending on the runtime that records them.
"""
from __future__ import annotations


class _NullSpan:
    """The shared do-nothing span returned while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()

_open = None        # (name, attrs) → span context while any sink is on


def set_opener(open_) -> None:
    """Install ``open_(name, attrs)`` as what :func:`span` opens (None:
    tracing off)."""
    global _open
    _open = open_


def opener():
    return _open


def span(name: str, **attrs):
    """Open a nestable span (the shared no-op while tracing is off)."""
    open_ = _open
    if open_ is None:
        return NULL_SPAN
    return open_(name, attrs)
