"""Device time of the embedding tables per optimizer step: the operations
in the traced window whose ``op_name`` lies in the ``embed`` scope
(``EmbeddingParameter``: hash, gather and relayout) or in its backward,
``transpose(jvp(embed))`` (the gradient scatter), per step of the complete
chunk executions, averaged over the chips (``harness/scopes.py``)."""
from harness import scopes


def reduce(run):
    return scopes.ms_per_step(run, "embed")
