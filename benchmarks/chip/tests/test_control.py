"""The comparison that decides ``correct`` must fail what it guards against.

A whole run of the harness at the rehearsal size on the CPU (the look for a
chip skipped), once sound, once with the fp8 control in the program's place,
and once for each fault a served cell can have, planted in the timed path:
a served token altered where the engine produces it, a decode step that
leaves the pools as they were, and cold pages staged back as zeros.  A
one-chip cell exchanges nothing between chips and takes no mean over its
batch, so those faults have no place here."""
import argparse
import time

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.compiles import Compiles

TIER20 = "smollm-360m.tier20.chat"
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def compiles():
    return Compiles()


def _run(compiles, workload=TIER20, seed=SEED, control=False):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=4.0,
                              trace=0, rehearse=True, trace_dir=None)
    return harness.run(args, time.perf_counter(), compiles=compiles,
                       control=control)


def test_sound_run_is_correct_and_the_control_is_not(compiles):
    r = _run(compiles, control=True)
    c = r["compared"]
    assert r["program"]["correct"], r["program"]
    assert c["slot_mismatches"]["value"] == 0
    assert c["requests_compared"]["value"] >= 1
    # the fp8 control in the program's place, scored by the same
    # comparison, comes out not correct
    assert r["correct"] is False, c
    assert c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]


def test_altered_token_is_not_correct(compiles, monkeypatch):
    from repro.serve.engine import ContinuousBatcher
    step = ContinuousBatcher.step

    def altered(self):
        out = step(self)
        for toks in self.outputs:
            if len(toks) == 3:          # every request's third token
                toks[-1] = (toks[-1] + 1) % self.cfg.vocab_size
        return out
    monkeypatch.setattr(ContinuousBatcher, "step", altered)
    r = _run(compiles)
    assert not r["correct"], r["compared"]


def test_cold_pages_staged_wrong_is_not_correct(compiles, monkeypatch):
    from repro.models.kvcache import PagedKVPools
    stage = PagedKVPools.stage_cold

    def zeros(self, entry):
        k, v = stage(self, entry)
        return k * 0, v * 0
    monkeypatch.setattr(PagedKVPools, "stage_cold", zeros)
    r = _run(compiles)
    assert not r["correct"], r["compared"]


def test_decode_step_that_keeps_its_state_is_not_correct(compiles,
                                                        monkeypatch):
    from repro.serve.engine import ContinuousBatcher
    decode = ContinuousBatcher._pool_decode_step

    def unchanged(self):
        # the forward's new KV is never taken back into the pools
        self.pool.adopt = lambda new_tree: None
        try:
            return decode(self)
        finally:
            del self.pool.adopt
    monkeypatch.setattr(ContinuousBatcher, "_pool_decode_step", unchanged)
    r = _run(compiles)
    assert not r["correct"], r["compared"]


def test_sample_has_every_prompt_bucket_for_every_seed():
    from benchmarks.chip import spec, traffic
    mix = spec.load_cell(TIER20).mix
    for seed in (0, 1, 2**31 + 5, 2**40):
        s = traffic.sample(mix, seed, 1000)
        assert sorted({r.prompt_len for r in s}) == \
            mix["prompt_tokens"]["values"]
        # every seed, the same work in the same order
        d, e = traffic.deck(mix, seed, 1000), traffic.deck(mix, seed + 1, 1000)
        assert [(r.prompt_len, r.budget) for r in d] == \
            [(r.prompt_len, r.budget) for r in e]
    assert not np.array_equal(traffic.deck(mix, 1, 1000)[0].tokens,
                              traffic.deck(mix, 2, 1000)[0].tokens)
