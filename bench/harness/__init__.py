"""Shared pieces of the on-chip benchmark: cell discovery, the device check,
seeded sessions, plain references' math, the comparison that decides
``correct``, and the reduction of profiler traces to device figures."""
