"""What the serving stack asks of a model, and the models that answer.

The engine (``serve/engine.py``), its programs (``programs/serve_defs.py``)
and the loaders name no model. They take a CONFIG, a dataclass that
answers:

* ``build()``            the flax module. ``apply({"params", "cache"},
  tokens [b, t], train=False, mutable=["cache", "counters"],
  block_table=, cache_pos=, last_pos=None)`` gives float32 logits
  [b, t, V] ([b, V] at ``last_pos``); its ``cache`` collection is the
  per-layer key/value state, its ``counters`` collection (it may have
  none) small integer arrays a decode step returns beside its tokens;
* ``decode_config()``    itself, sanitised for single-device decode;
* ``program_key()``      a hashable tuple that ``config_from_key`` turns
  back into the config: what device programs are keyed by;
* ``program_tag()``      what a program's name says of its dtypes;
* ``attend_paths()``     the paged attend's implementation a layer
  (``ops/paged_attention.py:paged_attend_path``);
* ``prepare_params()``   a parameter tree as this config serves it;
* the fields ``block_size`` (positions a row may reach), ``vocab_size``,
  ``page_size``, ``kv_pages``, ``weights_dtype``, ``kv_dtype``;
* ``prefill_pass(t)`` (optional): the positions a pass of a prefill of a
  bucket of ``t`` runs, for a model that takes a bucket in passes through
  all its layers and skips the passes that hold only the bucket's padding
  (``models/cohere2_moe.py:prefill_in_passes``; ``t`` itself where the
  bucket runs whole). The engine asks it to count the positions a
  dispatched prefill ran, ``EngineStats.prefill_tokens_run`` beside the
  padded ``prefill_tokens`` (``serve/engine.py:prefill_positions_run``); a
  config that does not answer is counted as running its buckets;
* ``prefill_counted(bucket, start, suffix)`` (optional): what a
  dispatched prefill of ``suffix`` tokens from position ``start``, padded
  to ``bucket``, would have counted: ``{"<layer>/<module>/<name>":
  integers}``, added to the decode steps' ``counters`` (a prefill program
  returns none). ``models/cohere2_moe.py:grouped_prefill_chunks``: the
  chunks the grouped kernel walked and how many without masks;
* ``fixed_row_cache`` (optional, default false): true where a row's
  cache is ONE block of fixed size whatever the row's length (a
  recurrent state: ``models/brumby.py``) and not a run of pages that
  grows by a position a token. The engine's one pool manager then makes
  a page the whole row (``page_size = block_size``, one block-table
  entry a row, ``kv_pages`` counts blocks: ``engine.fit_pool``), needs
  no copy-on-write page, serves no prefix of a prompt from a block and
  registers none (``_walk_prefix``), and refuses ``spec_tokens > 0`` (a
  state cannot be rewound). Allocation, the refcount, park / resume /
  ``release_parked``, the scrub of a quarantined row's block with the
  null block's zeros and ``kv_pool_bytes`` are the paged models' own.
  What the MODEL owes in return: its ``cache`` leaves are indexed by
  block on their first axis; block 0 stays zeros; a row at cursor 0 has
  no past, so a block another row left reads as zeros to it (the engine
  zeroes nothing at admission); a prefill bucket's padding (the
  positions past ``last_pos``) leaves the block as it is; a row whose
  table entry is 0 is not live and touches nothing.

A model whose page holds something other than heads but still ONE
position a row of the page (``models/kimi_k2.py``: a latent that all heads
share, one array a layer) needs no flag and nothing of the engine: page
plans, the prefix cache, copy-on-write, the scrub, parking and
``kv_pool_bytes`` are ``tree.map``s over whatever the ``cache`` collection
holds, indexed by page on the first axis. What such a MODEL owes: every
``cache`` leaf is ``[kv_pages, page_size, ...]``; a position's row is
written whole by the call that computes it; a call WITHOUT
``last_pos`` may hold several tokens a row (a speculative verify) and
must see each at ``cache_pos + j``; a call WITH ``last_pos`` may start at
``cache_pos > 0`` on pages another request wrote (a prefix hit), so its
attend reads the past from the pages and not from what the call itself
computed; and the pool's minor dimension is whole lane tiles (a row of
576 is copied, the whole pool, around every scatter on the chip, and a
kernel cannot copy part of a tile: ``ops/latent_attention.py:pool_lanes``).

What a prefill (a call WITH ``last_pos``) owes, whatever the pages hold:
the logits of position ``last_pos`` and every position up to it in the
pools. The positions past ``last_pos`` are the bucket's padding: a model
may write them (they lie past the row's cursor, masked until a decode
step overwrites them) or, where it takes the bucket in passes, skip the
passes that hold nothing else and leave their pages as they were. So what
lies past a row's cursor is NEVER read as a number, by any attend: a page
comes to a row with what its last holder left (the engine zeroes nothing
at admission), a decode step writes its position before it attends, and
the kernels and the sparse attend select a masked position's value away
(``paged_attention``'s ``vok``, ``sparse_attention``'s ``last`` and
``kept``; ``tests/test_prefill_passes.py`` plants NaN there). The gather
of a row's window, the path off the TPU, gives it a zero weight instead:
sound because a freed page holds finite numbers (a quarantined row's are
written over first, ``engine._scrub_pages``).

A model that keeps BOTH kinds of cache in one row (``models/
qwen3_next.py``: pages of keys and values in some layers, a recurrent
state in the others) says ``row_state = True``, names the entries of its
``cache`` collection that are indexed by STATE BLOCK and not by page
(``row_state_names()``) and carries a field ``state_blocks`` that the
engine sets beside ``kv_pages``. The engine's one pool manager then gives
a row ONE state block beside its run of pages: the block table grows a
last column that names it (so the decode state carries it on the device
as it carries the pages, a stopped row's is cleared with them, and a
parked row's snapshot keeps it); an admission is planned, and succeeds,
only with both to be had (``admit_probe`` says so before); ``release``,
``release_parked`` and the scrub of a quarantined row free or zero both;
no prefix of a prompt is served to such a row and none is registered
(the state at a prefix's end is not kept), so its pages go back to the
free list at release; ``spec_tokens > 0`` is refused as for
``fixed_row_cache``; ``kv_pool_bytes`` counts the state apart. What such
a MODEL owes: the named entries' leaves are indexed by state block on
their first axis and block 0 stays zeros; every other leaf is
``[kv_pages, page_size, ...]`` under the page models' rules, except that
a call with ``last_pos`` never starts on pages another request wrote; a
row at cursor 0 has no past, so a state block another row left reads as
zeros to it; a prefill bucket's padding leaves the state as it is; a row
whose state block is 0 is not live and touches nothing; several tokens a
row without ``last_pos`` are refused.

A model whose residual is SEVERAL streams a token (``models/xing4.py``:
four, mixed around every sub-layer by a hyper-connection) owes the engine
nothing and asks nothing of it: the streams are a value inside a program,
born from the embedding in the first block and summed before the head in
the last, so a program's arguments, its pools, its logits and its counters
are a one-stream model's. What grows is what a program holds at once (a
prefill pass carries ``hc_mult`` float32 copies of its positions'
hidden), which ``prefill_rows`` bounds.

A family's key starts with its ``model_type``; GPT-2's is its plain field
tuple, as it always was (its programs' keys and names did not move).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from .brumby import FAMILY as BRUMBY, BrumbyConfig
from .cohere2_moe import FAMILY as COHERE2_MOE, Cohere2MoeConfig
from .keye_vl2 import FAMILY as KEYE_VL2, KeyeVL2Config
from .kimi_k2 import FAMILY as KIMI_K2, KimiK2Config
from .nanogpt import (GPTConfig, sample_logits,  # noqa: F401 — re-exported
                      sample_rows)
from .qwen3_next import FAMILY as QWEN3_NEXT, Qwen3NextConfig
from .xing4 import FAMILY as XING4, Xing4Config

FAMILIES = {COHERE2_MOE: Cohere2MoeConfig, KEYE_VL2: KeyeVL2Config,
            BRUMBY: BrumbyConfig, KIMI_K2: KimiK2Config,
            QWEN3_NEXT: Qwen3NextConfig, XING4: Xing4Config}


def config_from_key(key: tuple):
    """The config a ``program_key()`` came from."""
    if key and isinstance(key[0], str) and key[0] in FAMILIES:
        return FAMILIES[key[0]](*key)
    return GPTConfig(*key)


def config_from_dict(fields: Dict[str, Any]):
    """A config from a captured run's or a worker's JSON: the family by
    ``model_type`` (absent: GPT-2), unknown keys ignored so that an
    older server can read a newer snapshot."""
    cls = FAMILIES.get(fields.get("model_type"), GPTConfig)
    names = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in fields.items() if k in names})


def attend_path_id(config) -> str:
    """One id for a model's paged attends: the distinct per-layer paths
    joined by ``+`` (``pallas_paged``; ``pallas_paged+pallas_paged_window``
    for a model that mixes full and windowed layers; ``gather``)."""
    return "+".join(sorted(set(config.attend_paths())))
