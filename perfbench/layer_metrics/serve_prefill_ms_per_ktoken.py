"""Layer: Serving engine. Milliseconds of ``serve.admit`` (a prefill and
the read-back of its first token) a thousand prompt tokens, over the
admissions that ended inside the window, from the spans' ``prompt_tokens``.
Moves ``serve_tokens_per_s``."""


def read(facts):
    a = facts.get("admit_spans")
    if not a or not a.get("prompt_tokens"):
        return None
    return 1e6 * a["seconds"] / a["prompt_tokens"]
