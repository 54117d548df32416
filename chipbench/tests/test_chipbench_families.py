"""The dense decoder family gives the harness what it took before the
families: the same config objects, operation counts, bytes and smoke sizes
for each configuration the benchmark had then.  Only those are pinned, so
that a configuration added later needs no edit here."""

import json
import pathlib

import pytest

import chipbench_smoke as smoke
from chipbench import common, flops

REPO = smoke.REPO
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((REPO / c["file"]).read_text()) for c in SPEC["configs"]}

# name -> train_step_flops(2, 2048), then wave_flops and wave_bytes at
# (16, 64, 192) and at (32, 512, 32), as flops.py counted them before
COUNTS = {
    "minicpm2b_l4": (13415881900032, 3757447839744, 289393606656, 9255711670272,
                     748922241024),
    "minicpm2b": (71602916032512, 21935502655488, 1590677471232, 87344124788736,
                  4719797895168),
    "granite8b_l16": (94009854787584, 29962228727808, 1924617207808, 123151878979584,
                      4327964147712),
    "minicpm2b_l3": (11799575396352, 3252501872640, 253246832640, 7086589083648,
                     638620139520),
}
# name -> (n_layers, d_model, n_heads, n_kv_heads, d_ff, vocab, head_dim, rope_theta)
ARCH = {
    "minicpm2b_l4": (4, 2304, 36, 36, 5760, 122753, 64, 1e4),
    "minicpm2b": (40, 2304, 36, 36, 5760, 122753, 64, 1e4),
    "granite8b_l16": (16, 4096, 32, 8, 14336, 49152, 128, 1e7),
    "minicpm2b_l3": (3, 2304, 36, 36, 5760, 122753, 64, 1e4),
}


def _traffic(name):
    return json.loads((REPO / "chipbench" / "traffic" / f"{name}.json").read_text())


def test_every_configuration_names_a_family_the_benchmark_has():
    for config in CONFIGS.values():
        family = common.family(REPO, config)
        assert pathlib.Path(family.__file__).stem == config["program"]["family"]


def test_a_family_the_benchmark_does_not_have_is_refused():
    with pytest.raises(common.SpecError, match="no_such_family"):
        common.family(REPO, {"name": "x", "program": {"family": "no_such_family"}})


DATACLASS_FAMILY = """from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Dims:
    width: int
    layers: list[int]
"""


def test_a_family_module_runs_once_and_may_define_a_dataclass(tmp_path):
    (tmp_path / "chipbench" / "families").mkdir(parents=True)
    (tmp_path / "chipbench" / "families" / "dc.py").write_text(DATACLASS_FAMILY)
    config = {"name": "x", "program": {"family": "dc"}}
    family = common.family(tmp_path, config)
    assert family.Dims(2, [1]).width == 2
    assert common.family(tmp_path, config) is family


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_dense_family_counts_and_config_as_before(name):
    config = CONFIGS[name]
    family = common.family(REPO, config)
    assert config["program"] == {"family": "dense"}
    assert family.__file__.endswith("families/dense.py")
    got = (family.train_step_flops(config, 2, 2048),
           family.wave_flops(config, 16, 64, 192), family.wave_bytes(config, 16, 64, 192),
           family.wave_flops(config, 32, 512, 32), family.wave_bytes(config, 32, 512, 32))
    assert got == COUNTS[name]
    # and at every shape its cells run
    for w in SPEC["workloads"]:
        if w["config"] != name:
            continue
        t = _traffic(w["traffic"])
        if t["kind"] == "train":
            assert (family.train_step_flops(config, t["batch"], t["seq"])
                    == flops.train_step_flops(config, t["batch"], t["seq"]))
        for s in t.get("waves", []):
            shape = (t["batch"], s["prompt"], s["output"])
            assert family.wave_flops(config, *shape) == flops.wave_flops(config, *shape)
            assert family.wave_bytes(config, *shape) == flops.wave_bytes(config, *shape)
    cfg = family.arch_config(config, schedule="wsd")
    assert cfg == common.arch_config(config, schedule="wsd")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab,
            cfg.head_dim, cfg.rope_theta) == ARCH[name]
    assert (cfg.family, cfg.tie_embeddings, cfg.schedule) == ("dense", True, "wsd")


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_dense_smoke_size_as_before(name):
    config = CONFIGS[name]
    small = smoke.small_config(config)
    kv = {"granite8b_l16": 1}.get(name, 4)  # 4 query heads a kv head, as granite's 32 / 8
    assert small == dict(config, hidden_size=64, intermediate_size=128, head_dim=16,
                         vocab_size=512, num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=kv)
