"""Least work of one training step of a click model over hashed tables.

Counted from the configuration's shapes and optimizer, for the whole job
(so sharding the work over chips never counts it twice), whatever
implements it:

- dense AdamW reads and writes the parameter and both moments of every row
  of every table: 24 bytes and about 12 FLOPs per float32 row;
- the batch: its four columns (13 bytes an item) and, per table, one row
  read for the forward pass and one gradient written for the update (8 bytes
  an item), with the model's own FLOPs per item for forward and backward.
"""
from __future__ import annotations

from harness import plain

ADAMW_BYTES_PER_ROW = 24
ADAMW_FLOPS_PER_ROW = 12
BATCH_BYTES_PER_ITEM = 13
TABLE_BYTES_PER_ITEM = 8


def table_rows(cfg) -> int:
    return sum(plain.hashed_rows(t["parameters"], t["compression_ratio"],
                                 cfg["table_row_multiple"])
               for t in cfg["tables"].values())


def least_work(cfg, flops_per_item: float):
    """(FLOPs, bytes) of one step."""
    rows = table_rows(cfg)
    items = int(cfg["batch"]) * int(cfg["positions"])
    tables = len(cfg["tables"])
    nbytes = (rows * ADAMW_BYTES_PER_ROW
              + items * (BATCH_BYTES_PER_ITEM + TABLE_BYTES_PER_ITEM * tables))
    flops = rows * ADAMW_FLOPS_PER_ROW + items * flops_per_item
    return float(flops), float(nbytes)
