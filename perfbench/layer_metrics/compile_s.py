"""Layer: Program registry. Seconds inside the registry's program builds
(compile, or load from the persistent cache) up to the end of the run.
Moves ``setup_s``."""


def read(facts):
    return facts.get("compile_s")
