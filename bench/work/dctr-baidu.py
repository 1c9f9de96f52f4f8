"""Least work of one ``dctr-baidu`` step: dense AdamW over one hashed table
and the batch (see ``harness/work.py``). A sigmoid and a Bernoulli NLL take
about 10 FLOPs an item forward and about twice that backward."""
from harness import work

FLOPS_PER_ITEM = 30


def least_work(cfg):
    return work.least_work(cfg, FLOPS_PER_ITEM)
