"""Kernel profiling hooks for ``kernels/ops.py`` dispatch sites.

:func:`annotate` / :func:`dispatch` wrap every kernel call in a
``jax.named_scope``, so the kernel's operations carry its name in their
HLO ``op_name`` and show up named in HLO dumps and profiler timelines
(``jax.profiler.trace``; ``obs.devtrace`` reads them). Scopes are
trace-time only: compiled programs pay nothing. Device time per kernel is
read from a profiler trace, not timed around the call.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Callable

import jax


@contextmanager
def annotate(name: str):
    """Named-scope annotation for a kernel region (profiler-visible)."""
    with jax.named_scope(name):
        yield


def dispatch(name: str, fn: Callable[[], object]):
    """Run one kernel dispatch under ``jax.named_scope(name)``. ``fn`` is
    a zero-arg closure."""
    with jax.named_scope(name):
        return fn()
