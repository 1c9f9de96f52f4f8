"""Least work of one ``ubm-baidu`` step: dense AdamW over one hashed table
and the batch (see ``harness/work.py``); the (K, K) examination table's 100
cells add nothing to count. Counted from the reference
(``configs/ubm-baidu.py``), an item takes about 20 FLOPs forward: gamma's
baseline and sigmoid (5), the product with theta_{k,k'} (1; the table's
sigmoid is taken once a step), the Bernoulli NLL's two logs and its terms
(8), the mask and the sum (2), the last-click update (2); and about twice
that backward."""
from harness import work

FLOPS_PER_ITEM = 60


def least_work(cfg):
    return work.least_work(cfg, FLOPS_PER_ITEM)
