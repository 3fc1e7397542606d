"""Pages the pools copied hot to cold per step of the window (the delta of
``PagedKVPools.stats["page_copies"]``).  Nothing to read where the pools
never used their cold tier."""
UNIT = "pages/step"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "page manager: models/kvcache.py PagedKVPools"
MOVES = "itl_p50_ms"


def read(run):
    if not sum(s.counters.get("cold_stages", 0) for s in run.steps):
        return None
    copies = sum(s.counters.get("page_copies", 0) for s in run.steps)
    return copies / len(run.steps)
