"""Spans the harness puts around its own calls into each layer. They are
``jax.profiler.TraceAnnotation``s, so they land on the profiler's clock
beside the device's operations and cost next to nothing when no trace is
being taken."""

from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation


def span(name: str) -> TraceAnnotation:
    return TraceAnnotation(name)


def wrap(obj, attr: str, name: str) -> None:
    """Put a span around ``obj.attr`` on this one instance (the traced
    jobs' drivers only; the timed window's are left as they are)."""
    inner = getattr(obj, attr)

    @functools.wraps(inner)
    def spanned(*args, **kwargs):
        with TraceAnnotation(name):
            return inner(*args, **kwargs)

    _install(obj, attr, spanned)


def wrap_generator(obj, attr: str, name: str) -> None:
    """As ``wrap`` for a method that returns a generator: one span for
    each step of it (the work between two yields)."""
    inner = getattr(obj, attr)

    @functools.wraps(inner)
    def spanned(*args, **kwargs):
        it = inner(*args, **kwargs)
        while True:
            with TraceAnnotation(name):
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item

    _install(obj, attr, spanned)


def _install(obj, attr: str, spanned) -> None:
    # What the instance itself held under that name (a kernel set in
    # __init__) is put back by ``unwrap``; a method of the class is not.
    spanned._bench_restore = obj.__dict__.get(attr)
    setattr(obj, attr, spanned)


def unwrap(obj, *attrs: str) -> None:
    for attr in attrs:
        spanned = obj.__dict__.pop(attr, None)
        if spanned is None:
            continue
        if not hasattr(spanned, "_bench_restore"):
            raise ValueError(f"{attr} of {obj!r} holds no span")
        if spanned._bench_restore is not None:
            setattr(obj, attr, spanned._bench_restore)
