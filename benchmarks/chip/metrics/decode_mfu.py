"""The decode step's share of the chip's peak: operations of the window's
decode-only steps (no prefill in the step; the family's ``decode_step``)
over their summed host time times peak bf16 FLOP/s."""
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
LAYER = "model step: models/model.py forward, decode"
MOVES = "itl_p50_ms"


def read(run):
    steps = [s for s in run.steps if s.prefill_tokens == 0 and s.decode_lengths]
    if not steps or run.peak is None:
        return None
    ops = sum(run.family.decode_step(run.shape, s.decode_lengths)
              for s in steps)
    secs = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * ops / (secs * run.peak["bf16_flops_per_s"])
