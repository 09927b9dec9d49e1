"""Host-side compile work, counted from JAX's own monitoring events and
its compiler's log: how many programs were traced, lowered and compiled
(or loaded from the persistent cache), the seconds each stage took, and
which programs missed the persistent cache, by name and key; beside it
the host's own share of a call: the process's CPU seconds, Python's
garbage collections (count, seconds, full ones) and the times the kernel
took the CPU from the process.  run.py logs it for each warm-up call and
each window call, where nothing should miss the cache but the re-trace
and cache load that every user call pays; a call slower than its
neighbours shows there what it spent the time on."""
from __future__ import annotations

import collections
import gc
import logging
import resource
import time

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
        self.gc_n = self.gc_full = 0
        self.gc_s = 0.0
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)
        self._usage = self._rusage()

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

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_n += 1
            self.gc_full += info["generation"] == 2
            self._gc_t0 = None

    @staticmethod
    def _rusage():
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return ru.ru_utime + ru.ru_stime, ru.ru_nivcsw

    def take(self) -> str:
        text = ", ".join(f"{k} {self.n[k]} ({self.s[k]:.3f} s)"
                         for k in _STAGES.values())
        text += f"; cache misses {len(self.misses)}"
        if self.misses:
            shown = self.misses[:SHOWN]
            text += f": {' '.join(shown)}"
            if len(self.misses) > SHOWN:
                text += " ..."
        usage = self._rusage()
        text += (f"; host cpu {usage[0] - self._usage[0]:.3f} s, gc "
                 f"{self.gc_n} ({self.gc_s:.3f} s, {self.gc_full} full), "
                 f"preempted {usage[1] - self._usage[1]}")
        self._usage = usage
        self.n.clear()
        self.s.clear()
        self.misses.clear()
        self.gc_n = self.gc_full = 0
        self.gc_s = 0.0
        return text
