"""The ``mla`` family (DeepSeek-V2-Lite): its configuration checks, the
pools-path run of its cell at the rehearsal size against its reference and
the fp8 control, ``run.py --rehearse`` end to end, and its counts."""
import argparse
import dataclasses
import json
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks.chip import harness, spec
from benchmarks.chip.compiles import Compiles
from benchmarks.chip.families import INTERFACE

CELL = "deepseek-v2-lite.tier20.chat"
SEED = 2**31 + 16


@pytest.fixture(scope="module")
def file():
    entry = {c["name"]: c for c in spec.benchmark()["configs"]}[
        "deepseek-v2-lite"]
    return entry["file"], json.loads((spec.ROOT / entry["file"]).read_text())


@pytest.fixture(scope="module")
def mla(file):
    return spec.family("mla", file[0])


def test_family_has_the_interface_and_the_cell_loads(mla, file):
    for attr in INTERFACE + ("latent_decode_step",):
        assert callable(getattr(mla, attr)), attr
    cell = spec.load_cell(CELL)
    assert cell.family is mla and cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert {"latent_decode_ms", "latent_decode_roofline", "expert_ms",
            "cold_stage_gb_per_step"} <= names
    assert not names & {"paged_decode_ms", "paged_decode_roofline"}


def test_published_config_applies_only_the_held_experts(mla, file):
    cfg = mla.program_config(file[1], rehearse=False)
    m = cfg.moe
    assert (m.num_experts, m.first_expert, m.num_held, m.experts_per_token,
            m.d_ff, m.num_shared_experts) == (64, 0, 8, 6, 1408, 2)
    assert m.dropless and not m.norm_topk_prob and cfg.kv_norm
    assert cfg.norm_eps == 1e-6 and cfg.rope_yarn.factor == 40
    s = mla.Shape.of(cfg)
    assert (s.held, s.experts, s.shared_ff, s.latent, s.rope) == \
        (8, 64, 2816, 512, 64)
    p = mla.param_count(s)
    assert 3.0e9 < p["stack"] + p["head"] + s.vocab * s.d_model < 3.2e9


@pytest.mark.parametrize("change, match", [
    ({"reduced": ["n_routed_experts", "num_hidden_layers"]},
     "cannot apply the cuts"),
    ({"reduced": []}, "not listed in reduced"),
    ({"kv_lora_rank": 256}, "kv_lora_rank"),
    ({"norm_topk_prob": True}, "norm_topk_prob"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
])
def test_config_checks_exit_naming_the_key(mla, file, change, match):
    config = dict(file[1], **change)
    with pytest.raises(SystemExit, match=match):
        mla.program_config(config, rehearse=False)


def test_program_without_the_mechanisms_exits(mla, file, monkeypatch):
    """A program with no YaRN or no held-expert layer (the tree before
    this family) exits at once instead of running another model."""
    from repro.configs import base
    real = base.get_config("deepseek-v2-lite-16b")
    monkeypatch.setattr(base, "get_config", lambda name: dataclasses.replace(
        real, rope_yarn=None))
    with pytest.raises(SystemExit, match="rope_scaling"):
        mla.program_config(file[1], rehearse=False)


def test_latent_decode_counts_by_hand(mla, file):
    s = mla.Shape.of(mla.program_config(file[1], rehearse=False))
    flops, nbytes = mla.latent_decode_call(s, [100, 28])
    # 128 rows of 576 lanes, each scored by 16 heads (512 + 64 lanes) and
    # summed as a 512-lane value: 16 x 128 x 2 x (576 + 512)
    assert flops == 16 * 128 * 2 * 1088
    # rows once (bf16) + 2 slots' queries (16 x 576) and outputs (16 x 512)
    assert nbytes == 128 * 576 * 2 + 2 * 16 * 1088 * 2
    assert mla.latent_decode_step(s, [100, 28]) == (27 * flops,
                                                    27 * nbytes)
    # a decode step of 2 slots: the held experts' gated MLPs at the
    # expected routed rows, 2 x 6 x 8 / 64 in each of the 26 expert layers
    assert mla.routed_rows(s, 2) == 1.5
    no_experts = dataclasses.replace(s, held=0)
    assert mla.decode_step(s, [100, 28]) - mla.decode_step(
        no_experts, [100, 28]) == 26 * 2 * 3 * 2048 * 1408 * 1.5


def test_pools_path_matches_reference_and_the_control_does_not():
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=2.0, trace=0,
                              rehearse=True, trace_dir=None)
    r = harness.run(args, time.perf_counter(), compiles=Compiles(),
                    control=True)
    assert r["program"]["correct"], r
    assert r["compared"]["requests_compared"]["value"] >= 1
    assert not r["correct"], r["compared"]           # the fp8 control
    assert r["compared"]["max_logit_gap"]["value"] > \
        r["compared"]["max_logit_gap"]["limit"] > \
        r["program"]["max_logit_gap"]


def test_run_rehearses_the_cell_end_to_end():
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", str(SEED + 1), "--seconds", "2", "--trace", "0",
         "--rehearse"], cwd=spec.ROOT, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"], result
    assert np.isfinite(result["compared"]["max_logit_gap"]["value"])
