"""Jaxpr program auditor: donation, host callbacks, f64, program keys.

Every compiled program the repo ships — the trainer step for each
strategy (the function ``NodeRuntime.compile`` jits under ``shard_map``),
the serving engine's paged-KV family (prefix-aware bucketed prefill,
copy-on-write page copy, fused ``decode_chunk`` decode, fused
draft+verify speculative decode) — is abstractly traced (never compiled
or executed) and checked:

- **Donation** — an argument donated via ``donate_argnums`` whose buffer
  XLA cannot alias to an output (no output with the same shape/dtype
  remains unmatched) is a *silently-unaliased donation*: the caller gave
  the buffer up, XLA copied anyway, and peak memory is what donation was
  supposed to save. Unused donated inputs are flagged too.
- **Host callbacks** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` in a hot-path program force a device→host round
  trip per dispatch and break async dispatch; the audit requires zero.
- **f64 upcasts** — any equation producing float64/complex128 outside an
  allowlist (a stray Python float in a jnp op under ``jax_enable_x64``
  doubles the payload of everything downstream).

Each program also gets a canonical **program key** =
``(name × static config × input shapes/dtypes × donation mask)`` whose
hash is the planned registry key for ROADMAP item 5 (the unified
device-program registry shared by trainer dispatch, the engine LRUs and
the persistent compile cache). ``recompile_guard`` reports key
collisions and *near misses* — two keys identical except for the
donation mask or a single dtype, the classic signature of an accidental
recompile (same logical program, different jit options).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

# program_key moved to gym_tpu.programs.keys so the device-program
# registry and this auditor compute THE SAME key from the same function
# — re-exported here for existing importers
from ..programs.keys import program_key  # noqa: F401  (re-export)
from .jaxpr_tools import (eval_shape_with_axis_env, trace_with_axis_env,
                          walk_jaxpr)

PyTree = Any


@dataclasses.dataclass
class Finding:
    """One audit violation."""

    program: str
    kind: str        # donation-unaliased | donation-unused | host-callback
    #                | f64-upcast
    detail: str

    def as_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ProgramSpec:
    """A shipped program, described for the auditor: the traceable
    function, its example argument templates (``ShapeDtypeStruct``
    pytrees), which positional args are donated (mirroring the real
    ``jax.jit``/``NodeRuntime.compile`` donation convention), and the
    static config that goes into the program key."""

    name: str
    fn: Callable
    args: Tuple[Any, ...]
    donate_args: Tuple[int, ...] = ()
    hot_path: bool = True
    axis_sizes: Optional[Dict[str, int]] = None
    config: Dict[str, Any] = dataclasses.field(default_factory=dict)
    family: str = ""


@dataclasses.dataclass
class ProgramAudit:
    name: str
    key: str                 # canonical descriptor (json)
    key_hash: str            # sha256[:16] — the registry key
    findings: List[Finding]
    n_eqns: int
    n_collectives: int
    family: str = ""

    @property
    def ok(self) -> bool:
        return not self.findings

    def as_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name, "family": self.family,
            "key_hash": self.key_hash, "ok": self.ok,
            "n_eqns": self.n_eqns, "n_collectives": self.n_collectives,
            "findings": [f.as_dict() for f in self.findings],
        }


def _count_eqns(jaxpr) -> int:
    from .jaxpr_tools import _sub_jaxprs

    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        n += sum(_count_eqns(s.jaxpr) for s in _sub_jaxprs(eqn.params))
    return n


def audit_program(spec: ProgramSpec,
                  f64_allow: Sequence[str] = ()) -> ProgramAudit:
    """Trace ``spec.fn`` abstractly and run every static check."""
    closed = trace_with_axis_env(spec.fn, spec.args, spec.axis_sizes)
    node_axes = tuple((spec.axis_sizes or {}).keys())
    report = walk_jaxpr(closed, node_axes=node_axes,
                        axis_sizes=spec.axis_sizes or {}, fold=False)
    findings: List[Finding] = []

    if spec.hot_path:
        for cb in report.callbacks:
            findings.append(Finding(
                spec.name, "host-callback",
                f"host callback staged in a hot-path program at {cb} — "
                f"each dispatch pays a device→host round trip"))

    allow = tuple(f64_allow)
    for site in report.f64_eqns:
        if any(a in site for a in allow):
            continue
        findings.append(Finding(
            spec.name, "f64-upcast",
            f"float64/complex128 produced at {site} (not in allowlist) — "
            f"silent 2× payload on everything downstream"))

    findings.extend(_audit_donation(spec, closed))

    key, key_hash = program_key(spec.name, spec.config, spec.args,
                                spec.donate_args)
    return ProgramAudit(
        name=spec.name, key=key, key_hash=key_hash, findings=findings,
        n_eqns=_count_eqns(closed.jaxpr),
        n_collectives=len(report.data_collectives()),
        family=spec.family or spec.name.split("[")[0])


def _audit_donation(spec: ProgramSpec, closed) -> List[Finding]:
    """Shape/dtype multiset matching between donated inputs and outputs
    (XLA's aliasing criterion), plus a consumed check on the flattened
    invars. The jaxpr invars are the flattened leaves of all positional
    args in order, which is how ``jax.jit`` resolves ``donate_argnums``
    to buffers."""
    findings: List[Finding] = []
    # flattened leaf spans per positional arg
    spans: List[Tuple[int, int]] = []
    off = 0
    for a in spec.args:
        n = len(jax.tree.leaves(a))
        spans.append((off, off + n))
        off += n
    invars = closed.jaxpr.invars
    if off != len(invars):
        # tokens/effects can extend invars; donation audit stays valid
        # for the leading arg leaves
        invars = invars[:off]

    used = set()
    for eqn in closed.jaxpr.eqns:
        for a in eqn.invars:
            used.add(id(a))
    outset = {id(v) for v in closed.jaxpr.outvars}

    out_pool: Dict[Tuple, int] = {}
    for ov in closed.jaxpr.outvars:
        aval = getattr(ov, "aval", None)
        if aval is None:
            continue
        k = (tuple(aval.shape), str(np.dtype(aval.dtype)))
        out_pool[k] = out_pool.get(k, 0) + 1

    for ai in spec.donate_args:
        lo, hi = spans[ai]
        for j, v in enumerate(invars[lo:hi]):
            aval = v.aval
            k = (tuple(aval.shape), str(np.dtype(aval.dtype)))
            if id(v) not in used and id(v) not in outset:
                findings.append(Finding(
                    spec.name, "donation-unused",
                    f"donated arg {ai} leaf {j} {k} is never consumed — "
                    f"the donation frees nothing and hides a dead input"))
                continue
            if out_pool.get(k, 0) > 0:
                out_pool[k] -= 1
            else:
                findings.append(Finding(
                    spec.name, "donation-unaliased",
                    f"donated arg {ai} leaf {j} {k} has no remaining "
                    f"output of the same shape/dtype — XLA cannot alias "
                    f"it and will silently copy (donation wasted)"))
    return findings


# -- the shipped-program registry -----------------------------------------


def _tiny_gpt_config():
    from ..models.nanogpt import GPTConfig

    return GPTConfig(block_size=32, vocab_size=64, n_layer=1, n_head=2,
                     n_embd=32, dropout=0.0, bias=True)


def trainer_step_specs(num_nodes: int = 4, n_micro: int = 1,
                       micro_bs: int = 2, seq_len: int = 16
                       ) -> List[ProgramSpec]:
    """One ProgramSpec per shipped strategy: the exact per-node function
    ``Trainer.fit`` hands to ``NodeRuntime.compile`` (``make_train_step``
    over the real GPT loss model), with the runtime's donation
    convention (``donate_state=True`` → arg 0, the TrainState)."""
    import jax.numpy as jnp

    from ..models.base import LossModel
    from ..models.nanogpt import GPT
    from ..train_node import make_init_fn, make_train_step
    from .jaxpr_tools import abstract_node_ctx
    from .trace_check import default_strategy_suite

    cfg = _tiny_gpt_config()
    loss_model = LossModel(GPT(cfg))
    x = jax.ShapeDtypeStruct((n_micro, micro_bs, seq_len), np.int32)
    batch_tpl = (x, x)
    # closed over by init_fn (not a traced argument), so it must be a
    # concrete array — a few hundred bytes of zeros
    ex = np.zeros((micro_bs, seq_len), np.int32)
    example_micro = (ex, ex)
    specs = []
    for name, strategy in default_strategy_suite().items():
        n_virt = 2 if name.endswith("_vnode") else 1
        ctx = abstract_node_ctx(num_nodes, n_virt=n_virt)
        strategy.finalize(64)
        strategy.bind_ctx(ctx)
        axis_sizes = dict(zip(ctx.axes, ctx.sizes))
        init_fn = make_init_fn(loss_model, strategy, example_micro,
                               seed=0, ctx=ctx)
        state_tpl = eval_shape_with_axis_env(
            init_fn, (jax.ShapeDtypeStruct((), np.int32),), axis_sizes)
        node_step = make_train_step(loss_model, strategy, ctx)
        specs.append(ProgramSpec(
            name=f"trainer.step[{name}]", fn=node_step,
            args=(state_tpl, batch_tpl), donate_args=(0,),
            axis_sizes=axis_sizes,
            config={"model": "gpt-tiny", "num_nodes": num_nodes,
                    **strategy.config()},
            family="trainer.step"))
    return specs


def _spec_from_def(pdef) -> ProgramSpec:
    """A registry ``ProgramDef`` as an auditable ``ProgramSpec`` — same
    name/config/templates/donation, so ``program_key`` over the spec and
    ``pdef.key()`` are the same key by construction."""
    return ProgramSpec(name=pdef.name, fn=pdef.builder(), args=pdef.args,
                       donate_args=pdef.donate_args, config=pdef.config,
                       family=pdef.family)


def engine_program_defs(num_slots: int = 2, decode_chunk: int = 4,
                        buckets: Sequence[int] = (8, 32),
                        page_size: int = 8, gamma: int = 4):
    """Every serving-engine program at the audit parameterization, as
    registry ``ProgramDef``s — enumerated through the device-program
    registry's public definitions (``gym_tpu.programs.serve_defs``),
    NOT private engine builders: the defs the auditor traces are the
    defs the engine acquires, so the audit key set and the registry key
    set cannot drift independently."""
    defs = paged_program_defs(num_slots=num_slots,
                              decode_chunk=decode_chunk,
                              buckets=buckets, page_size=page_size,
                              gamma=gamma)
    defs.extend(quantized_program_defs(num_slots=num_slots,
                                       decode_chunk=decode_chunk,
                                       buckets=buckets,
                                       page_size=page_size, gamma=gamma))
    return defs


def quantized_program_defs(num_slots: int = 2, decode_chunk: int = 4,
                           buckets: Sequence[int] = (8, 32),
                           page_size: int = 8, gamma: int = 4):
    """The quantized serving family (ISSUE 11) at the audit
    parameterization: int8 weights (per-tile QuantizeCodec storage with
    dequant fused into the consuming matmuls) + int8 paged KV (per-(page
    slot, head) scales). Same paged program set — prefix-aware prefill,
    CoW page copy, paged decode, fused draft+verify — over the quantized
    config, so donation discipline (the int8 pools AND their scale
    sidecars alias through every dispatch), callback freedom and f64
    hygiene are CI-gated for the quantized hot path exactly like the f32
    one. Names carry the dtype tag (``serve_defs._qtag``); keys differ
    from the f32 family through the config tuple."""
    import dataclasses as _dc

    from ..models.nanogpt import decode_config
    from ..programs import serve_defs as sd

    base = decode_config(_tiny_gpt_config())
    mb = base.block_size // page_size
    kv_pages = 2 + num_slots * mb
    cfg_tuple = _dc.astuple(
        _dc.replace(base, page_size=page_size, kv_pages=kv_pages,
                    weights_dtype="int8", kv_dtype="int8"))
    # an engine with gamma > 0 admits into the speculative state
    defs = [sd.paged_prefill_def(cfg_tuple, int(b), num_slots, hist=True)
            for b in buckets]
    defs.append(sd.cow_def(cfg_tuple))
    defs.append(sd.paged_decode_def(cfg_tuple, num_slots, decode_chunk))
    defs.append(sd.spec_decode_def(cfg_tuple, num_slots, decode_chunk,
                                   gamma))
    return defs


def paged_program_defs(num_slots: int = 2, decode_chunk: int = 4,
                       buckets: Sequence[int] = (8, 32),
                       page_size: int = 8, gamma: int = 4):
    """The paged-KV/speculative program family (ISSUE 7) as registry
    ``ProgramDef``s: prefix-aware paged prefill (per bucket), the
    copy-on-write page copy, the paged ``decode_chunk`` scan, and the
    fused draft+verify speculative program. All four DONATE the
    page-pool cache — it is the multi-MB buffer threaded linearly
    through every dispatch."""
    import dataclasses as _dc

    from ..models.nanogpt import decode_config
    from ..programs import serve_defs as sd

    base = decode_config(_tiny_gpt_config())
    mb = base.block_size // page_size
    kv_pages = 2 + num_slots * mb
    cfg_tuple = _dc.astuple(
        _dc.replace(base, page_size=page_size, kv_pages=kv_pages))
    # an engine with gamma > 0 admits into the speculative state
    defs = [sd.paged_prefill_def(cfg_tuple, int(b), num_slots, hist=True)
            for b in buckets]
    defs.append(sd.cow_def(cfg_tuple))
    defs.append(sd.paged_decode_def(cfg_tuple, num_slots, decode_chunk))
    defs.append(sd.spec_decode_def(cfg_tuple, num_slots, decode_chunk,
                                   gamma))
    return defs


def engine_program_specs(num_slots: int = 2, decode_chunk: int = 4,
                         buckets: Sequence[int] = (8, 32)
                         ) -> List[ProgramSpec]:
    """The serving engine's program families, traced exactly as the
    engine acquires them from the device-program registry, with their
    real donation masks: the pool, arg 1 (arg 0 of the page copy)."""
    return [_spec_from_def(d)
            for d in engine_program_defs(num_slots=num_slots,
                                         decode_chunk=decode_chunk,
                                         buckets=buckets)]


def paged_program_specs(num_slots: int = 2, decode_chunk: int = 4,
                        buckets: Sequence[int] = (8, 32),
                        page_size: int = 8, gamma: int = 4
                        ) -> List[ProgramSpec]:
    """Auditable specs for ``paged_program_defs`` (kept for direct
    use; ``engine_program_specs`` already includes them)."""
    return [_spec_from_def(d)
            for d in paged_program_defs(num_slots=num_slots,
                                        decode_chunk=decode_chunk,
                                        buckets=buckets,
                                        page_size=page_size,
                                        gamma=gamma)]


def elastic_program_specs() -> List[ProgramSpec]:
    """The elastic-membership redistribution family (ROADMAP: Elastic
    ZeRO) at its audit parameterization — flat ZeRO-slice re-partition,
    replicated-row re-replication, and the sharded-params unshard, each
    across uneven K→K' pairs. Enumerated through the SAME public defs
    the trainer's resume path acquires (``programs.elastic_defs``), so
    reshard keys cannot drift from what restore actually builds. The
    family takes host arrays from a checkpoint — nothing to donate —
    and must stay callback-free and f64-clean like every other shipped
    program."""
    from ..programs.elastic_defs import elastic_program_defs
    return [_spec_from_def(d) for d in elastic_program_defs()]


def shipped_programs(num_nodes: int = 4) -> List[ProgramSpec]:
    """Every compiled program the repo ships, audit-sized (tiny model:
    the checks are structural — donation masks, callback freedom, dtype
    discipline — and shape-independent)."""
    return (trainer_step_specs(num_nodes) + engine_program_specs()
            + elastic_program_specs())


def recompile_guard(audits: Sequence[ProgramAudit]) -> Dict[str, Any]:
    """Key-collision / near-miss report over a set of program audits.

    - ``collisions``: two DIFFERENT canonical descriptors hashing equal
      (must never happen), or the same program name audited twice with
      different keys (a recompile of the "same" program).
    - ``near_misses``: key pairs within one family identical except for
      the donation mask — the classic accidental-recompile cause (same
      logical program, different jit options ⇒ two executables)."""
    by_hash: Dict[str, str] = {}
    by_name: Dict[str, set] = {}
    collisions: List[str] = []
    for a in audits:
        prev = by_hash.get(a.key_hash)
        if prev is not None and prev != a.key:
            collisions.append(
                f"hash collision: {a.key_hash} maps to two descriptors")
        by_hash[a.key_hash] = a.key
        by_name.setdefault(a.name, set()).add(a.key_hash)
    for name, hashes in by_name.items():
        if len(hashes) > 1:
            collisions.append(
                f"program {name!r} produced {len(hashes)} distinct keys "
                f"— every re-audit should be key-stable")

    near: List[str] = []
    descs = [(a, json.loads(a.key)) for a in audits]
    for i in range(len(descs)):
        for j in range(i + 1, len(descs)):
            a, da = descs[i]
            b, db = descs[j]
            if a.family != b.family or a.key_hash == b.key_hash:
                continue
            same_but_donation = (
                da["in_avals"] == db["in_avals"]
                and da["config"] == db["config"]
                and da["donated"] != db["donated"])
            if same_but_donation:
                near.append(
                    f"{a.name} vs {b.name}: identical program, different "
                    f"donation mask — two executables for one program")
    return {"collisions": collisions, "near_misses": near,
            "n_keys": len(by_hash)}


def registry_key_reconciliation(audits: Sequence[ProgramAudit]
                                ) -> Dict[str, Any]:
    """CI gate (ISSUE 9): the auditor's serve-program key set must equal
    the key set a device-program registry derives from the SAME public
    defs.  Both paths run ``programs.keys.program_key``, so a mismatch
    means the audit's enumeration and the engine's acquisition path have
    drifted apart — exactly the bespoke-cache split the unified registry
    exists to prevent."""
    from ..programs import ProgramRegistry
    from ..programs.elastic_defs import elastic_program_defs

    reg = ProgramRegistry()
    for d in engine_program_defs():
        reg.register(d)
    for d in elastic_program_defs():
        reg.register(d)
    registry_keys = set(reg.keys())
    audit_keys = {a.key_hash for a in audits
                  if a.name.startswith(("serve.", "elastic."))}
    return {
        "n_registry_keys": len(registry_keys),
        "n_audit_serve_keys": len(audit_keys),
        "key_set_match": registry_keys == audit_keys,
        "only_in_audit": sorted(audit_keys - registry_keys),
        "only_in_registry": sorted(registry_keys - audit_keys),
    }


def audit_shipped_programs(num_nodes: int = 4) -> Dict[str, Any]:
    """Audit every shipped program; the CLI/CI entry point."""
    audits = [audit_program(s) for s in shipped_programs(num_nodes)]
    guard = recompile_guard(audits)
    registry = registry_key_reconciliation(audits)
    n_findings = sum(len(a.findings) for a in audits)
    return {
        "programs": [a.as_dict() for a in audits],
        "recompile_guard": guard,
        "registry": registry,
        "violations": (n_findings + len(guard["collisions"])
                       + (0 if registry["key_set_match"] else 1)),
    }
