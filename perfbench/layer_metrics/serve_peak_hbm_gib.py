"""Layer: Serving engine. Peak bytes in use on the chip when the window
closes (weights, page pool, the decode step's gather), from the
allocator. Moves ``serve_tokens_per_s``."""


def read(facts):
    if facts.get("kind") != "closed" or not facts.get("memory_peak_bytes"):
        return None
    return facts["memory_peak_bytes"] / 2 ** 30
