"""Device time of the fused loss kernels per optimizer step: the operations
in the traced window whose ``op_name`` lies in the ``loss`` scope
(``kernels/ops.py``: ``examination_nll``/``session_nll``, forward and their
custom backward), per step of the complete chunk executions, averaged over
the chips (``harness/scopes.py``)."""
from harness import scopes


def reduce(run):
    return scopes.ms_per_step(run, "loss")
