"""gym_tpu.programs — the unified device-program registry (ROADMAP 3).

One keyed, observable owner for every compiled XLA program the repo
dispatches: trainer steps, the serving engine's prefill/admit/decode
families, the paged/speculative programs, and the fleet hot-swap's warm
handoff.  See ``registry`` for the store, ``serve_defs`` for the engine
program definitions, ``warmup`` for background AOT precompilation, and
``keys`` for the canonical program key shared with the jaxpr auditor.
"""

from .elastic_defs import (elastic_program_defs, replicate_rows_def,
                           reshard_flat_def, unshard_params_def)
from .keys import program_key
from .registry import (DEFAULT_CACHE_DIR, Program, ProgramDef,
                       ProgramRegistry, compile_counter,
                       default_registry, disk_event_counters,
                       enable_disk_tier, resolve_cache_dir,
                       xla_compile_counter)
from .warmup import WarmupThread, warm_engine_programs

__all__ = [
    "program_key", "ProgramDef", "Program", "ProgramRegistry",
    "default_registry", "compile_counter", "xla_compile_counter",
    "enable_disk_tier", "resolve_cache_dir", "disk_event_counters",
    "DEFAULT_CACHE_DIR",
    "WarmupThread", "warm_engine_programs",
    "elastic_program_defs", "reshard_flat_def", "replicate_rows_def",
    "unshard_params_def",
]
