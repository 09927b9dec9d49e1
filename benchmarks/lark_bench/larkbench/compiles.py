"""Host-side compile work, counted from JAX's own monitoring events and
its compiler's log: how many programs were traced, lowered and compiled
(or loaded from the persistent cache), the seconds each stage took, and
which programs missed the persistent cache, by name and key.  run.py logs
it for each warm-up call and each window call, where nothing should miss
the cache but the re-trace and cache load that every user call pays."""
from __future__ import annotations

import collections
import logging

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_COMPILER_LOGGER = "jax._src.compiler"
_MISS = "PERSISTENT COMPILATION CACHE MISS"
SHOWN = 8           # misses named in a line, the first ones


class Counter:
    """Counts and seconds per stage, and the persistent cache's misses,
    since the last `take()`."""

    def __init__(self):
        import jax
        self.n = collections.Counter()
        self.s = collections.Counter()
        self.misses = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        # the compiler logs each persistent-cache miss, with its key, at
        # DEBUG; a filter reads them and passes on only what the logger
        # passed before, so nothing more is printed
        log = logging.getLogger(_COMPILER_LOGGER)
        self.level = log.getEffectiveLevel()
        log.setLevel(logging.DEBUG)
        log.addFilter(self._filter)

    def _on(self, event: str, seconds: float, **kwargs) -> None:
        stage = _STAGES.get(event)
        if stage is not None:
            self.n[stage] += 1
            self.s[stage] += seconds

    def _filter(self, record: logging.LogRecord) -> bool:
        if str(record.msg).startswith(_MISS):
            name, key = record.args
            self.misses.append(f"{name}:{key[-8:]}")
        return record.levelno >= self.level

    def take(self) -> str:
        text = ", ".join(f"{k} {self.n[k]} ({self.s[k]:.3f} s)"
                         for k in _STAGES.values())
        text += f"; cache misses {len(self.misses)}"
        if self.misses:
            shown = self.misses[:SHOWN]
            text += f": {' '.join(shown)}"
            if len(self.misses) > SHOWN:
                text += " ..."
        self.n.clear()
        self.s.clear()
        self.misses.clear()
        return text
