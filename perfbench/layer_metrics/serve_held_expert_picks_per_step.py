"""Layer: Expert layer. Token-picks a held expert a decode step, the mean
over the expert layers (those that count ``layers_<i>/mlp/picks``; a
leading dense layer counts none): how many rows share one read of an
expert's weights. 32 live rows x 8 picks over 384 routed experts give
0.67 here where the deployment's 32 chips' rows give 21. From the
program's counters. Moves ``serve_tokens_per_s``."""
import numpy as np


def read(facts):
    raw = facts.get("model_counters")
    steps = (facts.get("stats_delta") or {}).get("decode_steps")
    if not raw or not steps:
        return None
    picks = [np.asarray(raw[key], np.float64)
             for i in range(int(facts["sizes"]["num_hidden_layers"]))
             if (key := f"layers_{i}/mlp/picks") in raw]
    if not picks:
        return None
    return float(np.mean([p.mean() for p in picks]) / steps)
