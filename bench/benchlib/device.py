"""The device under test: what it is, its peaks, its memory, and the
compilations a window must not hold.

The persistent compilation cache sits at a fixed path inside the
checkout, ``.jax_cache/bench``, whatever the environment says: the path
is part of the cache's key, so only a directory that never moves is
found again by the next run.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, Optional

from benchlib.registry import BENCH_DIR, ROOT, BenchError

CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def use_compile_cache(path: str = CACHE_DIR) -> str:
    import jax
    # jax writes nothing into a directory that is not there
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however small or quick to compile, so that a
    # cell's second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts compilations (persistent-cache hits included) as they
    happen, so a driver can read how many fell inside its window."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


@dataclass
class Device:
    platform: str
    kind: str
    count: int

    def as_dict(self) -> Dict:
        return {"platform": self.platform, "kind": self.kind,
                "count": self.count}


def find_device(chips: int, require_tpu: bool = True) -> Device:
    """The devices JAX sees; a missing TPU or too few chips is an error."""
    import jax
    devs = jax.devices()
    d = Device(devs[0].platform, devs[0].device_kind, len(devs))
    if require_tpu and d.platform != "tpu":
        raise BenchError(f"no TPU: JAX found {d.count} {d.platform} "
                         "device(s)")
    if d.count < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{d.count}")
    return d


def peaks(kind: str) -> Dict:
    """Published peaks of one chip of ``kind``; a missing kind is an
    error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


def memory_peak_bytes(n_devices: Optional[int] = None) -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax
    best = None
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            best = max(best or 0, int(stats["peak_bytes_in_use"]))
    return best
