"""Layer: Step program. Device ms a step (chip 0) in the operations
traced under the ``strategy`` scope: gradient clipping, the inner
optimizer's update and what the strategy communicates (its collectives
across chips; DiLoCo's outer step in the steps that take it). A fusion
counts under the scope of its root operation. Moves
``train_tokens_per_s``."""
from perfbench import spans


def read(facts):
    return spans.scope_ms_per_step(facts, "strategy")
