"""Layer: Serving engine. Device ms of the prefill programs a thousand
prompt tokens: the time of every run of a program named ``*prefill*`` in
the traced stretch (a run whole: every layer's projections, the expansion
of keys and values, the attend, the experts, the head), over the prompt
tokens of the admissions that stretch held (the ``serve.admit`` spans'
``prompt_tokens``, ``admit_spans_traced``). ``serve_prefill_share_pct``
and ``serve_prefill_ms_per_ktoken`` read the admissions' HOST time; this
is what the chip spends. A prefill that straddles the stretch's edge is
counted whole and timed in part, or the other way round: read it over
several runs. Moves ``serve_tokens_per_s``."""


def read(facts):
    trace = facts.get("trace")
    tokens = (facts.get("admit_spans_traced") or {}).get("prompt_tokens")
    if trace is None or not tokens:
        return None
    seconds = sum(s for name, (_runs, s) in trace.module_runs.items()
                  if "prefill" in name)
    return 1e6 * seconds / tokens if seconds else None
