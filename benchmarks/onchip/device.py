"""The device: the check for a chip, the compile cache, compile counting,
peaks and memory."""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def require_chip(chips: int):
    """The devices JAX sees, or ``NoChip`` when they are not TPUs or are
    fewer than the cell asks for. The measurement never falls back."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; jax.devices()[0] is "
                     f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devices)}")
    return devices


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else one fixed directory in the checkout. Every program is
    cached, however fast it compiled, so that a second run compiles none."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Backend compiles JAX reports through ``jax.monitoring`` (a program
    read from the persistent cache is reported too)."""

    def __init__(self):
        import jax
        self.programs = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, seconds, **kwargs):
        if event == BACKEND_COMPILE:
            self.programs.append((str(kwargs.get("fun_name")), seconds))

    def count(self) -> int:
        return len(self.programs)


def peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][kind]


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    best = 0
    for d in devices:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)))
    return best


def fail(msg: str) -> int:
    print(f"benchmarks/onchip: {msg}", file=sys.stderr)
    return 2
