"""Compilations and persistent-cache traffic, from JAX's own monitoring
events, and the lowered text of every program (``jax_dump_ir_to``).

Copied from ``chip_smoke.py`` (PR 21): the yardstick may not live in the
program.  ``require_kernels`` proves from the lowered text that a Pallas kernel
is in a program the cell compiled, so a kernel that gave way to the jnp path
fails the cell.
"""
from __future__ import annotations

import os

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class Watch:
    def __init__(self, jax, dump_dir: str):
        self.compiles = self.hits = self.misses = 0
        self.compile_s = 0.0
        self.dump_dir = dump_dir
        jax.config.update("jax_dump_ir_to", dump_dir)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    def mark(self):
        return (self.compiles, self.compile_s, self.hits, self.misses,
                frozenset(self.modules()))

    def since(self, mark=(0, 0.0, 0, 0, frozenset())) -> dict:
        """Counters since ``mark`` (since the start, with none), and the
        first few programs lowered since."""
        c, s, h, m, modules = mark
        return {"compilations": self.compiles - c,
                "backend_compile_s": self.compile_s - s,
                "cache_hits": self.hits - h,
                "cache_misses": self.misses - m,
                "new_modules": sorted(set(self.modules()) - modules)[:8]}

    def modules(self):
        return sorted(os.listdir(self.dump_dir))


def kernel_calls(texts, kernels, interpreted: bool) -> dict:
    """Most calls of each kernel in one lowered program among ``texts``: the
    Mosaic custom call carrying the kernel's name or, where kernels are
    interpreted (a rehearsal), its name scope."""
    needles = {k: f"/{k}/pallas_call" if interpreted
               else f'kernel_name = "{k}"' for k in kernels}
    found = dict.fromkeys(kernels, 0)
    for text in texts:
        if interpreted or "@tpu_custom_call" in text:
            for k, needle in needles.items():
                found[k] = max(found[k], text.count(needle))
    return found


def require_kernels(watch: Watch, kernels, interpreted: bool) -> dict:
    def texts():
        for m in watch.modules():
            with open(os.path.join(watch.dump_dir, m)) as f:
                yield f.read()

    found = kernel_calls(texts(), kernels, interpreted)
    missing = [k for k, n in found.items() if n == 0]
    if missing:
        raise RuntimeError(f"kernel(s) {missing} are in no program the cell "
                           f"compiled: the jnp path took their place")
    return found
