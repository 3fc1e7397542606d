"""Each configuration file chooses its architecture's family module.

Every configuration in ``BENCHMARK.json`` resolves to a family with the
whole interface; an unknown family exits naming the file, and so does a
file whose ``reduced`` differs from its ``BENCHMARK.json`` entry's; ``gqa``
refuses every cut; its weights are the bits the harness made before
families existed; and a family that is new files alone, in a copy of the
benchmark's tree, is taken by the harness at the rehearsal size."""
import argparse
import hashlib
import json
import shutil
import time

import jax
import numpy as np
import pytest

from benchmarks.chip import harness, spec
from benchmarks.chip.compiles import Compiles
from benchmarks.chip.families import INTERFACE

CONFIGS = {c["name"]: c for c in spec.benchmark()["configs"]}
SEED = 2**31 + 15


def _digest(named) -> str:
    h = hashlib.sha256()
    for name, a in named:
        h.update(name.encode())
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_configuration_resolves_to_a_whole_family(name):
    file = CONFIGS[name]["file"]
    config = json.loads((spec.ROOT / file).read_text())
    fam = spec.family(config["family"], file)
    for attr in INTERFACE:
        assert callable(getattr(fam, attr)), attr
    cfg = fam.program_config(config, rehearse=True)
    s = fam.Shape.of(cfg)
    hash(s)
    assert (s.vocab, s.layers) == (cfg.vocab_size, cfg.num_layers)
    w = fam.make(s, SEED)
    params = fam.to_program(w, s)
    assert jax.tree.leaves(params)
    assert fam.param_count(s)["stack"] > 0
    tokens = np.arange(12, dtype=np.int32) % s.vocab
    logits = fam.logits_at(s, w, tokens, np.arange(4, 12), 16, 8)
    assert logits.shape == (8, s.vocab) and np.isfinite(logits).all()
    assert fam.decode_step(s, [5, 9]) > 0
    # every per-layer reader of the family's counts finds them
    cells = {c["name"] for c in spec.benchmark()["workloads"]
             if c["config"] == name}
    for m in spec.benchmark()["per_layer"]:
        if m["name"] == "paged_decode_roofline" and \
                cells & set(m["workloads"]):
            assert fam.paged_decode_step(s, [5, 9])[0] > 0


@pytest.mark.parametrize("key, value", [
    ("num_hidden_layers", 4),           # depth
    ("vocab_size", 4096),               # a slice of the vocabulary
    ("hidden_size", 480),               # a width: never cut
])
def test_gqa_refuses_every_cut_the_file_lists(key, value):
    file = CONFIGS["smollm-360m"]["file"]
    config = json.loads((spec.ROOT / file).read_text())
    config.update({key: value, "reduced": [key]})
    gqa = spec.family("gqa", file)
    with pytest.raises(SystemExit, match="cannot apply the cuts"):
        gqa.program_config(config, rehearse=False)


# sha256 of the plain weights (by sorted name) and of the program's tree
# (by key path) at the rehearsal size and SEED, as the harness made them
# before the GQA code moved into families/gqa.py
PARENT = {
    "smollm-360m": (
        "e7635359e831d4c42f583062696dad2a4a61cc0d609270c2a63eeb16c84b6618",
        "428d4849d57abd1819b6b1d71420de15995d7ef6adc5c9fc45f29ceba7ac6459"),
    "internlm2-1.8b": (
        "13c6023abc3c35386fa3fa2594fb2f4db232d334307188003c6bd8526cdf6890",
        "f7a950ae78d45a3f9c7ee9f20436b9d4986ecf170577a4a0663853f8bee84c4d"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_gqa_weights_are_the_parents_bits(name):
    config = json.loads((spec.ROOT / CONFIGS[name]["file"]).read_text())
    gqa = spec.family("gqa", CONFIGS[name]["file"])
    s = gqa.Shape.of(gqa.program_config(config, rehearse=True))
    w = gqa.make(s, SEED)
    tree = jax.tree_util.tree_flatten_with_path(gqa.to_program(w, s))[0]
    got = (_digest((k, w[k]) for k in sorted(w)),
           _digest((jax.tree_util.keystr(p), a) for p, a in tree))
    assert got == PARENT[name]


@pytest.fixture
def copy_tree(tmp_path, monkeypatch):
    """A copy of ``BENCHMARK.json`` and the benchmark's directory that the
    harness reads in place of the repository's; returns a function that
    adds a configuration, its file and one cell on ``tier20.chat``."""
    here = tmp_path / spec.HERE.relative_to(spec.ROOT)
    shutil.copytree(spec.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    monkeypatch.setattr(spec, "HERE", here)

    def add(name: str, family: str, like: str, **keys) -> str:
        config = json.loads((here / "configs" / f"{like}.json").read_text())
        config.update(keys, family=family)
        file = f"{here.relative_to(tmp_path)}/configs/{name}.json"
        (tmp_path / file).write_text(json.dumps(config))
        bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
        entry = dict(CONFIGS[like], name=name, file=file)
        bench["configs"].append(entry)
        cell = f"{name}.tier20.chat"
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "tier20.chat", "chips": 1,
                                   "why": "a family added as new files"})
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        return cell
    add.here = here
    return add


def test_unknown_family_exits_naming_the_file(copy_tree):
    cell = copy_tree("stray", "nosuch", like="smollm-360m")
    with pytest.raises(SystemExit, match=r"configs/stray\.json: unknown "
                                         r"family 'nosuch'"):
        spec.load_cell(cell)


def test_reduced_that_differs_from_benchmark_exits_naming_the_file(
        copy_tree):
    # the file's own list is what the family applies; BENCHMARK.json's is
    # what is checked against the source: they may not disagree
    cell = copy_tree("cut", "gqa", like="smollm-360m", num_hidden_layers=4,
                     reduced=["num_hidden_layers"])
    with pytest.raises(SystemExit, match=r"configs/cut\.json: reduced "
                                         r"\['num_hidden_layers'\] but "
                                         r"BENCHMARK\.json lists \[\]"):
        spec.load_cell(cell)


def test_a_new_family_is_new_files_only(copy_tree):
    shutil.copy(copy_tree.here / "families" / "gqa.py",
                copy_tree.here / "families" / "gqa_copy.py")
    cell = copy_tree("copy-360m", "gqa_copy", like="smollm-360m")
    fam = spec.load_cell(cell).family
    assert fam.__file__ == str(copy_tree.here / "families" / "gqa_copy.py")
    args = argparse.Namespace(workload=cell, seed=SEED + 1, seconds=2.0,
                              trace=0, rehearse=True, trace_dir=None)
    r = harness.run(args, time.perf_counter(), compiles=Compiles())
    assert r["correct"], r["compared"]
    assert r["compared"]["requests_compared"]["value"] >= 1
