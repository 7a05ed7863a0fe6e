"""Layer: Device placement. Peak bytes in use on the fullest chip after
the timed fit, from the allocator. Moves ``train_tokens_per_s``."""


def read(facts):
    if facts.get("kind") != "fit" or not facts.get("memory_peak_bytes"):
        return None
    return facts["memory_peak_bytes"] / 2 ** 30
