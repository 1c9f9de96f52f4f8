"""Device time of the optimizer per optimizer step: the operations in the
traced window whose ``op_name`` lies in the ``update`` scope
(``TrainEngine._apply_update``, dense and sparse routes), per step of the
complete chunk executions, averaged over the chips (``harness/scopes.py``)."""
from harness import scopes


def reduce(run):
    return scopes.ms_per_step(run, "update")
