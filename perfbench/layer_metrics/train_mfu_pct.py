"""Layer: Step program. Model FLOP/s utilisation of the traced run: the
benchmark's own operations per token (forward and backward, attention
included, recomputation not counted) times the run's tokens per second,
over the chips' bf16 peak. Moves ``train_tokens_per_s``."""
from perfbench import flops


def read(facts):
    if facts.get("kind") != "fit":
        return None
    per_token = flops.train_flops_per_token(
        facts["sizes"], facts["sizes"]["n_positions"])
    peak = flops.peaks(facts["device_kind"])["bf16_flops"] * facts["chips"]
    return 100.0 * per_token * facts["tokens_per_s"] / peak
