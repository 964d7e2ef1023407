"""Whether the program is steady: counts of what must not happen in a
measured window.

A reading is steady when, while it ran, JAX traced, lowered and compiled
nothing, the persistent compilation cache gained no entry, and no signature
bucket was dispatched for the first time in this process.  Warm-up is the
cell's own traffic until three consecutive readings are steady, so there is
no list of shapes beside the traffic that a change to the cutover or the
buckets could make stale.
"""

from __future__ import annotations

import os
from typing import Iterable

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def cache_dir() -> str:
    """Where the program keeps JAX's persistent compilation cache
    (``stellar_tpu/ops/__init__.py``): ``JAX_COMPILATION_CACHE_DIR`` where
    set, else ``<checkout>/.jax_cache``."""
    import jax

    return str(jax.config.jax_compilation_cache_dir or "")


class Watch:
    """Counts compilations, cache entries and first bucket dispatches."""

    def __init__(self):
        from jax import monitoring

        self.compile_events = 0
        self.compile_seconds = 0.0
        self.buckets: set = set()

        def on_duration(event: str, seconds: float, **_kw) -> None:
            if event in _COMPILE_EVENTS:
                self.compile_events += 1
                self.compile_seconds += seconds

        monitoring.register_event_duration_secs_listener(on_duration)

    def cache_entries(self) -> int:
        try:
            return sum(1 for n in os.listdir(cache_dir()) if not n.endswith(".tmp"))
        except OSError:
            return 0

    def note_spans(self, spans: Iterable) -> int:
        """Feed the program's spans; returns how many buckets were
        dispatched for the first time among them."""
        new = 0
        for s in spans:
            if s.name == "ed25519.device_dispatch" and s.attrs:
                b = s.attrs.get("bucket")
                if b is not None and b not in self.buckets:
                    self.buckets.add(b)
                    new += 1
        return new

    def mark(self) -> tuple:
        return (self.compile_events, self.cache_entries())

    def since(self, mark: tuple) -> dict:
        return {
            "compile_events": self.compile_events - mark[0],
            "cache_entries": self.cache_entries() - mark[1],
        }
