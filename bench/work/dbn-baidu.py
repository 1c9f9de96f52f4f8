"""Least work of one ``dbn-baidu`` step: dense AdamW over two hashed tables
and the batch (see ``harness/work.py``). The DBN's conditional chain takes
about 30 FLOPs an item forward (three sigmoids, the examination update and
the Bernoulli NLL) and about twice that backward."""
from harness import work

FLOPS_PER_ITEM = 90


def least_work(cfg):
    return work.least_work(cfg, FLOPS_PER_ITEM)
