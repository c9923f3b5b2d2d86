"""Host spans the benchmark records around its calls into the program.

Each span is a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so
it lands in the profiler's trace on the same clock as the device's
operations, and the trace reduction can say what the host was doing in
each idle gap of the device. Spans are recorded only in traced runs.

A wrapped function is wrapped in every run, traced or not. Pallas kernels
carry the Python frames they were traced under into the compiled
program, so a wrapper present only in traced runs would change the
compile cache's key and make the first traced run compile again.
"""
from __future__ import annotations

import contextlib
import functools

PREFIX = "bench."


class Spans:
    def __init__(self, on: bool):
        self.on = on

    @contextlib.contextmanager
    def __call__(self, name: str):
        if not self.on:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield

    def wrap(self, module, attr: str, name: str) -> None:
        """Record a span around every call of ``module.attr`` (a function
        the program calls through its module's namespace), when spans are
        on. Does nothing when the function is not there."""
        fn = getattr(module, attr, None)
        if fn is None:
            return

        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with self(name):
                return fn(*args, **kwargs)

        setattr(module, attr, spanned)
