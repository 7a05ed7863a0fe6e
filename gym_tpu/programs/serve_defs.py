"""The serving engine's device programs, as registry ``ProgramDef``s.

This is the single source of truth for every program the inference
engine dispatches — the prefix-aware paged prefill (one per bucket), the
copy-on-write page copy, the fused ``decode_chunk`` scan over the page
pool and the fused draft+verify speculative decode, for whichever model
the program key names (``models/serving.py``).  ``serve/engine.py``
acquires them through the registry (replacing its six retired
module-global ``lru_cache`` stores) and ``analysis/jaxpr_audit.py``
enumerates them through the same functions — so the auditor's key set
and the registry's key set are the same set by construction, and a
program signature drifting between the two is impossible rather than
merely tested.

Each ``ProgramDef`` carries the EXACT argument avals its engine call
site dispatches with: the registry AOT-compiles against these templates
and stores the ``Compiled`` executable, so a mismatch fails loudly at
the first dispatch instead of silently recompiling.

The builder bodies are documented where the semantics live:
``serve/engine.py``'s module docstring (the program-set design) and the
per-builder docstrings below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ``sample_logits`` is the row sampler the programs hand ``sample_rows``,
# looked up in THIS module when a program is traced: what replaces it here
# (the benchmark's altered-token control) alters every token of every
# program, whichever branch the batch's sampler takes
from ..models.serving import config_from_key, sample_logits, sample_rows
from .registry import ProgramDef

# -- aval templates --------------------------------------------------------


def _scalar(dt):
    return jax.ShapeDtypeStruct((), dt)


def _vec(n, dt):
    return jax.ShapeDtypeStruct((n,), dt)


_KEY_T = jax.ShapeDtypeStruct((2,), np.uint32)

# The decode programs' per-slot state, by name.  A decode program takes
# it as ONE dict and returns it whole: what it advanced (``tok``,
# ``active``, ``gen_idx``, ``remaining``, ``pos``; speculative also
# ``hist``; the block table with the rows that stopped cleared) and,
# unchanged, what only an admission writes (``base_keys``, ``eos``,
# ``temp``, ``top_k``, ``top_p``).  A prefill program takes it too and
# returns it with the admitted slot's row set, so the state threads
# prefill -> decode -> prefill ON THE DEVICE: the returned dict is the
# next dispatch's argument as it is, and the engine hands over a NumPy
# array in an entry's place only where the host itself wrote a live row
# since (``serve/engine.py``: the host's mirrors, and when they go up).
PAGED_STATE = ("tok", "active", "base_keys", "gen_idx", "remaining", "eos",
               "temp", "top_k", "top_p", "bt", "pos")
SPEC_STATE = PAGED_STATE + ("hist",)


def _state_tpl(names, s: int, mb: int = 0, block_size: int = 0) -> dict:
    tpl = {"tok": _vec(s, np.int32), "active": _vec(s, np.bool_),
           "base_keys": jax.ShapeDtypeStruct((s, 2), np.uint32),
           "gen_idx": _vec(s, np.int32), "remaining": _vec(s, np.int32),
           "eos": _vec(s, np.int32), "temp": _vec(s, np.float32),
           "top_k": _vec(s, np.int32), "top_p": _vec(s, np.float32),
           "bt": jax.ShapeDtypeStruct((s, mb), np.int32),
           "pos": _vec(s, np.int32),
           "hist": jax.ShapeDtypeStruct((s, block_size), np.int32)}
    return {name: tpl[name] for name in names}


def _qtag(cfg_tuple: tuple) -> str:
    """What a program's NAME says of its model and dtypes (the config's
    own ``program_tag``): two variants of one program must not share a
    name, the auditor's recompile guard treats same-name-different-key as
    a collision."""
    return config_from_key(cfg_tuple).program_tag()


def _model(cfg_tuple: tuple):
    """``(config, module)`` of a program key: the programs name no model
    (``models/serving.py`` says what they ask of one)."""
    cfg = config_from_key(cfg_tuple)
    return cfg, cfg.build()


@functools.lru_cache(maxsize=64)
def _templates(cfg_tuple: tuple, batch: int):
    """``(params_tpl, pool_tpl)`` aval pytrees for a ``batch``-row
    engine under this config — host-side ``eval_shape`` only, nothing
    compiles.  Bounded lru: entries are tiny aval trees, keyed by full
    config, and 64 far exceeds the distinct (config × batch) pairs any
    process serves."""
    cfg, model = _model(cfg_tuple)
    dummy = jnp.zeros((batch, 1), jnp.int32)
    mb = table_width(cfg)
    shapes = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, dummy, train=False,
            block_table=jnp.zeros((batch, mb), jnp.int32),
            cache_pos=jnp.zeros((batch,), jnp.int32)))
    return shapes["params"], shapes["cache"]


# -- builders (the jitted closures the registry compiles) ------------------


def build_paged_prefill(cfg_tuple: tuple, bucket: int):
    cfg, model = _model(cfg_tuple)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, state, slot, bt_row, start, tokens,
                true_suffix, key, temp, top_k, top_p, max_new, eos,
                prompt=None):
        """Prefix-aware paged prefill: process only the SUFFIX tokens the
        prefix cache could not supply. ``tokens`` [1, bucket] is the
        right-padded suffix, ``start`` [1] the first suffix position
        (= the shared-prefix length; attention gathers the resident
        prefix K/V through ``bt_row``), ``true_suffix`` its unpadded
        length. Samples the request's first token (key-schedule index 0)
        at the true last prompt position and returns it with the updated
        pool — the pool is DONATED: suffix K/V scatter in place.

        ADMISSION HAPPENS HERE: ``state`` is the decode programs' state
        and comes back with row ``slot`` set to the admitted request
        (input token = the sampled first token, cursor = the prompt's
        length, key index 1, ``max_new - 1`` tokens left, its sampling
        vectors, base key and block-table row; ``active`` unless that
        first token already ended it, by the decode programs' own rule).
        No host array of the state is written for an admission, so the
        decode step that follows takes all of it from the device. A
        speculative engine's state holds the token history: ``prompt``
        [block_size] (the whole prompt, zero-padded) becomes its row."""
        last, varsc = model.apply(
            {"params": params, "cache": cache}, tokens, train=False,
            mutable=["cache"], block_table=bt_row, cache_pos=start,
            last_pos=true_suffix - 1)                                # [1,V]
        tok, _sorted = sample_rows(
            last, jax.random.fold_in(key, 0)[None], temp[None], top_k[None],
            top_p[None], jnp.ones((1,), bool), sample_logits)
        first = tok[0].astype(jnp.int32)
        n = start[0] + true_suffix
        left = max_new - 1
        live = ~((left <= 0) | ((eos >= 0) & (first == eos)))
        row = {"tok": first, "active": live, "base_keys": key,
               "gen_idx": 1, "remaining": left, "eos": eos, "temp": temp,
               "top_k": top_k, "top_p": top_p, "pos": n,
               "bt": jnp.where(live, bt_row[0], 0)}
        if prompt is not None:
            row["hist"] = prompt.at[n].set(first)
        state = {name: arr.at[slot].set(row[name])
                 for name, arr in state.items()}
        return tok, state, varsc["cache"]

    return prefill


def build_cow(cfg_tuple: tuple):
    """Copy page ``src`` → ``dst`` across every layer's K/V pool: the
    copy-on-write primitive for a shared block that must be appended
    into (re-forwarding its tokens into the shared page instead would
    perturb every other reader by the recompute's rounding)."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def cow(cache, src, dst):
        return jax.tree.map(lambda c: c.at[dst].set(c[src]), cache)

    return cow


def build_paged_decode(cfg_tuple: tuple, num_slots: int, chunk: int):
    """``chunk`` decode steps for the whole slot batch in ONE dispatch
    (a ``lax.scan``, amortizing per-dispatch overhead the way
    ``generate_fast``'s whole-request scan does). Each scanned step
    feeds every slot its current token and samples its next with its own
    key/params; K/V flow through the page pool via each slot's block
    table and the per-row cursor is explicit carry state (``pos``).
    Slot lifecycle bookkeeping runs ON DEVICE so no host round trip is
    needed mid-chunk: a slot that hits EOS or exhausts ``remaining``
    flips inactive and freezes — its token and cursor stop advancing (no
    overflow creep, no garbage emission; its masked compute is the price
    of the fixed shape until the next admit). Inactive rows have their
    tables redirected to the NULL page so their garbage writes can never
    touch a page that was freed and reallocated to a live slot.

    ``decode(params, cache, state)`` with the ``PAGED_STATE`` dict;
    returns ``(read, logits, state, cache)``:

    - ``read``: the few small arrays the host downloads after every
      step: ``toks`` / ``emitted`` [chunk, S] (``emitted`` marks which
      scanned steps each slot was active for; the host replays it to
      route tokens to requests), the final ``tok`` / ``active`` /
      ``pos`` [S], ``nan_seen`` [S] (non-finite logits while the row
      was active, latched per scanned step: no path reads logits to
      decide anything), ``sorted`` [chunk] (whether the scanned step's
      sampler took its sorts: ``sample_rows`` decides that once a step
      for the whole batch, from the live rows' ``top_k`` and ``top_p``)
      and ``counted``: what the model counted (its ``counters``
      collection), summed over the chunk;
    - ``logits`` [S, V]: the last scanned step's, left on the device
      (teacher forcing and tests fetch them);
    - ``state``: the argument with what this dispatch advanced, the
      next dispatch's argument as it is. A row that stopped (EOS, its
      budget, non-finite logits: ``nan_seen`` rows clear their own
      ``active``) has its block-table row cleared, as the host clears
      its mirror's when it frees the pages."""
    cfg, model = _model(cfg_tuple)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, cache, state):
        bt, base_keys, eos = state["bt"], state["base_keys"], state["eos"]
        temp, top_k, top_p = state["temp"], state["top_k"], state["top_p"]

        def body(carry, _):
            cache, tok, act, pos, gidx, rem, nanc, _lg = carry
            bt_eff = jnp.where(act[:, None], bt, 0)
            logits, varsc = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                train=False, mutable=["cache", "counters"],
                block_table=bt_eff, cache_pos=pos)
            lg = logits[:, 0]                           # [S, V]
            # quarantine is latched PER ITERATION while the row is
            # active: the null-page redirect means a finished row's
            # later iterations read clean garbage, so the LAST step's
            # logits cannot witness a poison that struck mid-chunk
            bad = act & ~jnp.isfinite(lg).all(axis=-1)
            nanc = nanc | bad
            keys = jax.vmap(jax.random.fold_in)(base_keys, gidx)
            nxt, srt = sample_rows(lg, keys, temp, top_k, top_p, act,
                                   sample_logits)
            nxt = jnp.where(act, nxt, tok).astype(jnp.int32)
            emitted = act
            pos = jnp.where(act, pos + 1, pos)
            gidx = jnp.where(act, gidx + 1, gidx)
            rem = jnp.where(act, rem - 1, rem)
            # a poisoned row stops itself: the quarantine is no host write
            done = act & ((rem <= 0) | ((eos >= 0) & (nxt == eos)) | bad)
            # what the model counted this step (``counters``: small
            # integer arrays, or nothing), summed over the chunk below
            return ((varsc["cache"], nxt, act & ~done, pos, gidx, rem,
                     nanc, lg),
                    (nxt, emitted, srt, varsc.get("counters", {})))

        lg0 = jnp.zeros((num_slots, cfg.vocab_size), jnp.float32)
        nan0 = jnp.zeros((num_slots,), bool)
        (cache, tok, active, pos, gen_idx, remaining, nan_seen, lg), \
            (toks, emitted, srt, counted) = jax.lax.scan(
                body, (cache, state["tok"], state["active"], state["pos"],
                       state["gen_idx"], state["remaining"], nan0, lg0),
                None, length=chunk)
        counted = jax.tree.map(lambda c: c.sum(axis=0), counted)
        read = {"toks": toks, "emitted": emitted, "tok": tok,
                "active": active, "pos": pos, "nan_seen": nan_seen,
                "sorted": srt, "counted": counted}
        state = {**state, "tok": tok, "active": active, "pos": pos,
                 "gen_idx": gen_idx, "remaining": remaining,
                 "bt": jnp.where(active[:, None], bt, 0)}
        return read, lg, state, cache

    return decode


def _ngram_draft(hist, hist_len, tok, gamma: int):
    """Vectorized n-gram (prompt-lookup) drafting: for each slot, find
    the most recent earlier occurrence of the current BIGRAM
    ``(hist[len-2], tok)`` in that slot's token history and propose the
    ``gamma`` tokens that followed it. No match (or a match with no
    continuation) falls back to repeating ``tok`` — correctness never
    depends on draft quality, only throughput does: the verify step
    samples every position from the true conditional with the request's
    own key schedule, so ANY draft sequence yields the exact
    non-speculative token stream."""
    s, length = hist.shape
    idx = jnp.arange(length - 1)
    a = jnp.take_along_axis(
        hist, jnp.clip(hist_len - 2, 0, length - 1)[:, None], axis=1)[:, 0]
    m = (hist[:, :-1] == a[:, None]) & (hist[:, 1:] == tok[:, None])
    # strictly BEFORE the current bigram (which always matches itself)
    m = m & (idx[None, :] + 1 < hist_len[:, None] - 1)
    has = m.any(axis=1)
    j = jnp.max(jnp.where(m, idx[None, :], -1), axis=1)   # latest match
    dpos = j[:, None] + 2 + jnp.arange(gamma)[None, :]
    d = jnp.take_along_axis(hist, jnp.clip(dpos, 0, length - 1), axis=1)
    ok = has[:, None] & (dpos < hist_len[:, None])
    return jnp.where(ok, d, tok[:, None]).astype(jnp.int32)


def build_spec_decode(cfg_tuple: tuple, num_slots: int, chunk: int,
                      gamma: int):
    """Self-drafting speculative decoding (arXiv 2302.01318), fused into
    the ``decode_chunk`` scan: each scanned iteration drafts ``gamma``
    tokens per slot by n-gram lookup over the slot's own token history,
    scores ``[tok, d_1..d_γ]`` in ONE batched ``γ+1``-token model call,
    then runs the vectorized accept/reject entirely on device.

    EXACTNESS (stronger than the usual greedy-only guarantee): position
    ``i``'s token is sampled from the true conditional
    ``p(· | prefix, accepted_{<i})`` with the request's own key
    ``fold_in(base, gen_idx+i)`` — the draft only decides how many of
    those samples one dispatch may keep (the leading run where
    ``sampled_i == draft_i``, plus one bonus token at the first
    mismatch). The emitted stream is therefore IDENTICAL to the
    non-speculative engine for EVERY sampling configuration, not just
    greedy. Rejected drafts need no page copy: the rollback is a cursor
    rewind — their K/V sit beyond the new cursor in slot-owned blocks,
    causally masked until overwritten (exactly how padded prefill K/V
    are retired).

    ``spec(params, cache, state)`` with the ``SPEC_STATE`` dict; returns
    ``(read, logits, state, cache)`` as the paged decode does, ``toks``
    and ``emitted`` [chunk, S, γ+1]. The token history ``hist`` is part
    of the state and stays on the device, grown by what each iteration
    emitted; the host replays the same tokens into its own copy."""
    cfg, model = _model(cfg_tuple)
    g1 = int(gamma) + 1

    @functools.partial(jax.jit, donate_argnums=(1,))
    def spec(params, cache, state):
        bt, base_keys, eos = state["bt"], state["base_keys"], state["eos"]
        temp, top_k, top_p = state["temp"], state["top_k"], state["top_p"]

        def body(carry, _):
            cache, tok, act, pos, gidx, rem, hist, nanc, _lg = carry
            hist_len = pos + 1                # prompt + emitted count
            drafts = _ngram_draft(hist, hist_len, tok, gamma)   # [S, γ]
            inp = jnp.concatenate([tok[:, None], drafts], axis=1)
            bt_eff = jnp.where(act[:, None], bt, 0)
            logits, varsc = model.apply(
                {"params": params, "cache": cache}, inp, train=False,
                mutable=["cache"], block_table=bt_eff, cache_pos=pos)
            # latched per-iteration quarantine (see the paged decode
            # program) — position 0 only: later positions may be
            # LEGALLY NaN from the per-position window-overflow poison
            # on rejected drafts, while position 0 is always in-window
            # for an active row
            bad = act & ~jnp.isfinite(logits[:, 0]).all(axis=-1)
            nanc = nanc | bad
            idxs = gidx[:, None] + jnp.arange(g1)[None, :]
            keys = jax.vmap(jax.vmap(jax.random.fold_in,
                                     in_axes=(None, 0)))(base_keys, idxs)
            sampled, srt = sample_rows(logits, keys, temp, top_k, top_p,
                                       act, sample_logits)     # [S, γ+1]
            match = (sampled[:, :gamma] == drafts).astype(jnp.int32)
            acc = jnp.cumprod(match, axis=1).sum(axis=1)        # [S]
            m = acc + 1                       # leading matches + bonus
            pidx = jnp.arange(g1)[None, :]
            is_eos = (eos[:, None] >= 0) & (sampled == eos[:, None])
            eos_hit = is_eos & (pidx < m[:, None])
            any_eos = eos_hit.any(axis=1)
            m = jnp.where(any_eos, jnp.argmax(eos_hit, axis=1) + 1, m)
            m = jnp.minimum(m, rem)           # max-tokens cap
            m = jnp.where(act, m, 0)
            emit = (pidx < m[:, None]) & act[:, None]           # [S, γ+1]
            new_tok = jnp.take_along_axis(
                sampled, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
            new_tok = jnp.where(act, new_tok, tok).astype(jnp.int32)
            rem = rem - m
            done = act & ((rem <= 0) | any_eos | bad)
            # history grows by the emitted tokens so the NEXT iteration's
            # draft can match against them
            rows = jnp.arange(num_slots)[:, None]
            hpos = jnp.clip(hist_len[:, None] + pidx, 0,
                            cfg.block_size - 1)
            hist = hist.at[rows, hpos].set(
                jnp.where(emit, sampled, hist[rows, hpos]))
            lg = logits[:, 0]                 # teacher-forcing observable
            return ((varsc["cache"], new_tok, act & ~done, pos + m,
                     gidx + m, rem, hist, nanc, lg), (sampled, emit, srt))

        lg0 = jnp.zeros((num_slots, cfg.vocab_size), jnp.float32)
        nan0 = jnp.zeros((num_slots,), bool)
        (cache, tok, active, pos, gen_idx, remaining, hist, nan_seen,
         lg), (toks, emit, srt) = jax.lax.scan(
                body, (cache, state["tok"], state["active"], state["pos"],
                       state["gen_idx"], state["remaining"], state["hist"],
                       nan0, lg0), None, length=chunk)
        # ``counted``: what a model counts (nothing is counted here)
        read = {"toks": toks, "emitted": emit, "tok": tok,
                "active": active, "pos": pos, "nan_seen": nan_seen,
                "sorted": srt, "counted": {}}
        state = {**state, "tok": tok, "active": active, "pos": pos,
                 "gen_idx": gen_idx, "remaining": remaining, "hist": hist,
                 "bt": jnp.where(active[:, None], bt, 0)}
        return read, lg, state, cache

    return spec


# -- ProgramDefs -----------------------------------------------------------


def _paged_cfg(cfg_tuple: tuple):
    cfg = config_from_key(cfg_tuple)
    if not cfg.page_size or not cfg.kv_pages:
        raise ValueError(
            "paged program defs need a config with page_size/kv_pages "
            "set (the engine's dataclasses.replace'd decode config)")
    mb = table_width(cfg)
    pcfg = {"config": cfg_tuple, "page_size": cfg.page_size,
            "kv_pages": cfg.kv_pages}
    return cfg, mb, pcfg


def paged_prefill_def(cfg_tuple: tuple, bucket: int, num_slots: int = 1,
                      hist: bool = False) -> ProgramDef:
    """The prefill of one ``bucket`` for an engine of ``num_slots`` rows
    (the decode state it writes the admitted row into has that many);
    ``hist``: the state is the speculative programs', with the token
    history."""
    cfg, mb, pcfg = _paged_cfg(cfg_tuple)
    params_tpl, pool_tpl = _templates(cfg_tuple, 1)
    s = int(num_slots)
    names = SPEC_STATE if hist else PAGED_STATE
    tag = f",slots={s}" + (",hist" if hist else "")
    return ProgramDef(
        name=f"serve.paged_prefill[bucket={bucket}{tag}{_qtag(cfg_tuple)}]",
        family="serve.paged_prefill",
        config={**pcfg, "bucket": bucket, "num_slots": s, "hist": hist},
        args=(params_tpl, pool_tpl, _state_tpl(names, s, mb, cfg.block_size),
              _scalar(np.int32),
              jax.ShapeDtypeStruct((1, mb), np.int32),
              jax.ShapeDtypeStruct((1,), np.int32),
              jax.ShapeDtypeStruct((1, int(bucket)), np.int32),
              _scalar(np.int32), _KEY_T, _scalar(np.float32),
              _scalar(np.int32), _scalar(np.float32),
              _scalar(np.int32), _scalar(np.int32))
        + ((_vec(cfg.block_size, np.int32),) if hist else ()),
        donate_args=(1,),
        builder=lambda: build_paged_prefill(cfg_tuple, int(bucket)))


def cow_def(cfg_tuple: tuple) -> ProgramDef:
    cfg, _mb, pcfg = _paged_cfg(cfg_tuple)
    _, pool_tpl = _templates(cfg_tuple, 1)
    return ProgramDef(
        name=f"serve.cow[page={cfg.page_size}{_qtag(cfg_tuple)}]", family="serve.cow",
        config=pcfg,
        args=(pool_tpl, _scalar(np.int32), _scalar(np.int32)),
        donate_args=(0,),
        builder=lambda: build_cow(cfg_tuple))


def paged_decode_def(cfg_tuple: tuple, num_slots: int,
                     chunk: int) -> ProgramDef:
    _cfg, mb, pcfg = _paged_cfg(cfg_tuple)
    params_tpl, pool_tpl = _templates(cfg_tuple, num_slots)
    s = num_slots
    return ProgramDef(
        name=f"serve.paged_decode[slots={s},chunk={chunk}{_qtag(cfg_tuple)}]",
        family="serve.paged_decode",
        config={**pcfg, "num_slots": s, "decode_chunk": chunk},
        args=(params_tpl, pool_tpl, _state_tpl(PAGED_STATE, s, mb)),
        donate_args=(1,),
        builder=lambda: build_paged_decode(cfg_tuple, s, chunk))


def spec_decode_def(cfg_tuple: tuple, num_slots: int, chunk: int,
                    gamma: int) -> ProgramDef:
    cfg, mb, pcfg = _paged_cfg(cfg_tuple)
    params_tpl, pool_tpl = _templates(cfg_tuple, num_slots)
    s = num_slots
    return ProgramDef(
        name=f"serve.spec_decode[slots={s},chunk={chunk},gamma={gamma}{_qtag(cfg_tuple)}]",
        family="serve.spec_decode",
        config={**pcfg, "num_slots": s, "decode_chunk": chunk,
                "gamma": gamma},
        args=(params_tpl, pool_tpl,
              _state_tpl(SPEC_STATE, s, mb, cfg.block_size)),
        donate_args=(1,),
        builder=lambda: build_spec_decode(cfg_tuple, s, chunk, gamma))


# -- a row that holds a state block beside its pages ------------------------
#
# Everything below stands at the END of this file on purpose, and nothing
# above it may gain or lose a line: a Pallas kernel's compiled body carries
# the file and LINE of every frame it was traced under (this module's
# ``decode`` / ``prefill`` among them), so a line that moves up there moves
# the compile-cache key of every kernel-bearing program of every served
# model (``tests/test_serve_hybrid_pool.py`` holds the lines where they
# are).


def row_state(config) -> bool:
    """Whether a row of this model holds ONE state block beside its run
    of pages (``models/serving.py``: ``row_state``). Its block table then
    has one column more than the row has pages, the state block's: the
    decode state's ``bt`` is ``table_width`` columns wide, so the programs
    above carry it, clear it when a row stops and hand it to the model
    with the pages, and ask nothing else of it."""
    return bool(getattr(config, "row_state", False))


def table_width(config) -> int:
    """Columns of a row's block table: its pages and, for a model with
    ``row_state``, its state block."""
    return config.block_size // config.page_size + int(row_state(config))


def build_row_cow(cfg_tuple: tuple, state: bool):
    """``build_cow`` for a model with ``row_state``: the copy of entry
    ``src`` over ``dst`` runs over the leaves indexed by page only, or with
    ``state`` over those indexed by state block only (the scrub of a
    quarantined row's block): an index of one kind means nothing to the
    other kind's leaves."""
    names = set(config_from_key(cfg_tuple).row_state_names())

    @functools.partial(jax.jit, donate_argnums=(0,))
    def cow(cache, src, dst):
        return {name: jax.tree.map(lambda c: c.at[dst].set(c[src]), sub)
                if (name in names) == state else sub
                for name, sub in cache.items()}

    return cow


def row_cow_def(cfg_tuple: tuple, state: bool) -> ProgramDef:
    """``cow_def`` for a model with ``row_state``: its pages' copy, or
    with ``state`` its state blocks'."""
    cfg, _mb, pcfg = _paged_cfg(cfg_tuple)
    _, pool_tpl = _templates(cfg_tuple, 1)
    what = "state" if state else f"page={cfg.page_size}"
    return ProgramDef(
        name=f"serve.cow[{what}{_qtag(cfg_tuple)}]", family="serve.cow",
        config={**pcfg, "state": state},
        args=(pool_tpl, _scalar(np.int32), _scalar(np.int32)),
        donate_args=(0,),
        builder=lambda: build_row_cow(cfg_tuple, state))
