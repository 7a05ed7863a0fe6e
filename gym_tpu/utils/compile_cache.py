"""Persistent XLA compilation cache wiring (registry-owned).

The fit loop's warmup cost is dominated by XLA compiles of the node
program (a cell's ``compile_s``, PERF.md); JAX's persistent compilation
cache makes repeated invocations of the same program — re-running a
cell, iterating on a training script, resuming from a checkpoint
— skip straight to execution.

Since ISSUE 9 the knob is OWNED by the unified device-program registry
(``gym_tpu.programs.registry.enable_disk_tier``): the registry's
persistent executable tier and this helper are the same JAX compilation
cache, configured in one place, with hit/miss monitoring installed so
``programs.xla_compile_counter()`` can attribute deserializations vs
real compiles.  This module stays as the stable entry point of
``Trainer.fit`` and simply delegates.
"""

from __future__ import annotations

from typing import Optional

from ..programs.registry import DEFAULT_CACHE_DIR  # noqa: F401 (re-export)


def enable_compilation_cache(
    cache_dir: Optional[str] = None,
    *,
    min_compile_time_secs: Optional[float] = None,
) -> str:
    """Point JAX's persistent compilation cache at ``cache_dir``.

    Idempotent; safe to call before or after backend initialization (the
    cache is consulted lazily at the first compile). Returns the resolved
    directory. ``min_compile_time_secs=0`` caches even sub-second
    compiles — useful for CPU test/bench programs; by default JAX only
    persists compiles above ~1 s (``None`` leaves JAX's threshold
    untouched). Delegates to the device-program registry's
    ``enable_disk_tier`` — one owner for the disk tier.
    """
    from ..programs.registry import enable_disk_tier

    return enable_disk_tier(cache_dir,
                            min_compile_time_secs=min_compile_time_secs)
