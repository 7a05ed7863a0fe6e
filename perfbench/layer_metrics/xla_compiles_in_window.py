"""Layer: Program registry. XLA compile requests stamped inside the
measured window, whether the persistent cache answered them or not. Must
read 0. Moves ``setup_s``."""


def read(facts):
    return facts.get("xla_compiles_in_window")
