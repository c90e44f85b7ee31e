"""Persistent XLA compilation cache for the repo's entry points.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``benchmarks/simspeed.py``, the examples) calls :func:`enable` first thing
in ``main``; library modules and tests never do.

  * ``JAX_COMPILATION_CACHE_DIR`` set: the cache lives there and nowhere
    else.
  * unset: the cache lives at ``<repo>/.jax_cache`` (gitignored). The path
    is fixed because it is part of what a later run has to find again: a
    directory named after a temp file, a pid or the time never hits.

A cold program at the headline sweep shape compiles in tens of seconds, so
a second run in the same checkout (or under the same variable) skips most
of its set-up. :func:`counting` says whether it did.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path
from typing import Dict, Iterator

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def counting() -> Iterator[Dict[str, int]]:
    """Count the persistent-cache hits and misses of the compiles inside
    the block; yields the live {"hits": n, "misses": n} dict."""
    counts = dict.fromkeys(_EVENTS.values(), 0)

    def listener(event: str, **_) -> None:
        if event in _EVENTS:
            counts[_EVENTS[event]] += 1

    jax.monitoring.register_event_listener(listener)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_listener(listener)
