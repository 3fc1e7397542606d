"""Bytes the pools staged from host memory into HBM per step of the window
(the delta of ``PagedKVPools.stats["cold_stage_bytes"]``), in GB.  Nothing
to read where no cold page was staged."""
UNIT = "GB/step"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "page manager: models/kvcache.py PagedKVPools"
MOVES = "itl_p50_ms"


def read(run):
    staged = sum(s.counters.get("cold_stage_bytes", 0) for s in run.steps)
    return staged / len(run.steps) / 1e9 if staged else None
