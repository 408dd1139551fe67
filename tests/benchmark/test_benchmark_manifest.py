"""BENCHMARK.json and every data file load, cross-refer and keep to the
characters the manifest allows; and a cell, a configuration and a
per-layer metric can be ADDED as files without editing any existing one.
"""
import copy
import hashlib
import json
import os
import shutil

import pytest

import benchmarks.readers
from benchmarks import manifest, result


SERVE = "opt-6.7b.serve.chat"
CANDIDATE = manifest.with_candidate(manifest.load_manifest(), SERVE)


def test_manifest_is_sound():
    assert manifest.check_manifest() == []


def test_manifest_shape():
    man = manifest.load_manifest()
    assert man["command"] == ["python3", "benchmarks/run.py"]
    assert man["paths"] == ["benchmarks", "tests/benchmark"]
    assert 1 <= man["run_seconds"] <= 51
    assert len(json.dumps(man)) < 64 * 1024
    assert [m["name"] for m in man["end_to_end"]] == [
        "train.records_per_s_per_chip", "setup_s"]
    assert len(man["per_layer"]) == 6
    assert [(w["name"], w["chips"]) for w in man["workloads"]] == [
        ("opt-1.3b.train.seq2048", 1), ("opt-1.3b.train.dp4", 4)]


def test_the_candidate_merges_into_a_sound_manifest():
    """``opt-6.7b.serve.chat`` ran on the chip but cannot be admitted
    (benchmarks/candidates/): merging its entries must give a manifest
    that keeps every rule."""
    before = manifest.load_manifest()
    man = CANDIDATE
    assert manifest.check_manifest(man) == []
    assert [m["name"] for m in man["end_to_end"]] == [
        "train.records_per_s_per_chip", "setup_s", "serve.ttft_p95_ms",
        "serve.tpot_p95_ms"]
    assert len(man["per_layer"]) == 11
    assert len(man["workloads"]) == 3
    loaded = manifest.load_cell(SERVE, man)
    assert loaded["kind"] == "serve" and loaded["chips"] == 1
    dp4 = manifest.load_cell("opt-1.3b.train.dp4", man)
    assert dp4["chips"] == 4
    assert {m["name"] for m in dp4["per_layer"]} >= {
        "collective.exposed_share", "step.device_ms"}
    assert manifest.with_candidate(man, SERVE) == man     # idempotent
    assert before == manifest.load_manifest()             # not mutated


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  CANDIDATE["workloads"]])
def test_every_cell_resolves(cell):
    loaded = manifest.load_cell(cell, CANDIDATE)
    names = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert loaded["per_layer"], "a cell reports a per-layer metric"
    for spec in loaded["per_layer"]:
        reader = manifest.plugin("readers", spec["reader"])
        assert callable(reader.read)
        assert spec["moves"] in names, (
            f"{spec['name']} moves {spec['moves']}, which {cell} does "
            "not report")
    manifest.plugin("kinds", loaded["kind"])
    manifest.plugin("builders", loaded["config"]["builder"])
    manifest.plugin("reference", loaded["config"]["reference"])


@pytest.mark.parametrize("cfg", ["opt-1.3b", "opt-6.7b"])
def test_config_widths_are_the_published_ones(cfg):
    """Every width equals the model's own config.json (arXiv:2205.01068
    Table 1); only what ``reduced`` lists differs, and it lists no
    width."""
    published = {
        "opt-1.3b": dict(hidden_size=2048, ffn_dim=8192,
                         num_attention_heads=32, vocab_size=50272,
                         max_position_embeddings=2048,
                         word_embed_proj_dim=2048, num_hidden_layers=24),
        "opt-6.7b": dict(hidden_size=4096, ffn_dim=16384,
                         num_attention_heads=32, vocab_size=50272,
                         max_position_embeddings=2048,
                         word_embed_proj_dim=4096, num_hidden_layers=32),
    }[cfg]
    body = manifest.data_file("configs", cfg)
    for key, value in published.items():
        if key in body["reduced"]:
            assert body["published"][key] == value
            assert body[key] < value
        else:
            assert body[key] == value, key
    for key in body["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), key
        assert key in body["reduced_why"]
    assert body["assumed"] and body["stands_for"]


def test_bad_names_and_units_are_refused():
    assert manifest.valid_name("opt-1.3b.train.seq2048")
    for bad in ("has space", "a,b", "a/b", "", "x" * 65, "-lead"):
        assert not manifest.valid_name(bad)
    assert manifest.valid_unit("records/s/chip")
    for bad in ("tokens per second", "µs", ""):
        assert not manifest.valid_unit(bad)
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no.such.cell")


def _digest(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def test_adding_a_cell_config_and_metric_edits_no_existing_file(
        tmp_path, monkeypatch):
    """A later PR brings its own cell, configuration, traffic mix and
    per-layer metric (with a new reader) as NEW files and new manifest
    entries; the harness finds them by name."""
    root = tmp_path / "benchmarks"
    for d in ("configs", "traffic", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(manifest.ROOT, d), root / d)
    before = _digest(root)
    man = copy.deepcopy(manifest.load_manifest())

    cfg = manifest.data_file("configs", "opt-1.3b")
    cfg.update(name="throwaway-cfg", source="https://example.org/x")
    (root / "configs" / "throwaway-cfg.json").write_text(json.dumps(cfg))
    traffic = manifest.data_file("traffic", "pretrain-seq2048")
    traffic.update(name="throwaway-traffic", seq_len=1024)
    (root / "traffic" / "throwaway-traffic.json").write_text(
        json.dumps(traffic))
    cell = {"name": "throwaway.cell", "config": "throwaway-cfg",
            "traffic": "throwaway-traffic", "chips": 1, "kind": "train",
            "why": "a throw-away cell"}
    (root / "workloads" / "throwaway.cell.json").write_text(
        json.dumps(cell))
    metric = {"name": "throwaway.metric", "layer": "training loop",
              "unit": "steps", "better": "higher",
              "source": "program_counter",
              "moves": "train.records_per_s_per_chip",
              "reader": "throwaway_reader", "params": {"scale": 2}}
    (root / "layer_metrics" / "throwaway.metric.json").write_text(
        json.dumps(metric))
    readers = tmp_path / "readers"
    readers.mkdir()
    (readers / "throwaway_reader.py").write_text(
        "def read(rec, params):\n"
        "    return params['scale'] * len(rec['steps'])\n")
    monkeypatch.setattr(benchmarks.readers, "__path__",
                        list(benchmarks.readers.__path__) + [str(readers)])

    man["configs"].append({"name": "throwaway-cfg",
                           "source": cfg["source"],
                           "file": "benchmarks/configs/throwaway-cfg.json",
                           "reduced": cfg["reduced"], "why": "test"})
    man["workloads"].append({k: cell[k] for k in
                             ("name", "config", "traffic", "chips", "why")})
    for m in man["end_to_end"]:
        if m["name"] == "train.records_per_s_per_chip":
            m["workloads"].append("throwaway.cell")
    man["per_layer"].append({k: metric[k] for k in
                             ("name", "unit", "better", "source", "layer",
                              "moves")} | {"workloads": ["throwaway.cell"]})

    assert manifest.check_manifest(man, str(root)) == []
    loaded = manifest.load_cell("throwaway.cell", man, str(root))
    assert loaded["traffic"]["seq_len"] == 1024
    assert [m["name"] for m in loaded["per_layer"]] == ["throwaway.metric"]
    rec = {"loaded": loaded, "steps": [{}] * 3, "checks": {"ok": True},
           "compiles_in_window": 0, "attempted": 3, "failed": 0,
           "memory_peak_bytes": 1, "trace_events": None,
           "device": {"platform": "tpu", "kind": "TPU v5 lite",
                      "count": 1}}
    line = result.assemble(rec, traced=True, rehearsal=True,
                           log=lambda *_: None)
    assert line["metrics"] == {"throwaway.metric": {"value": 6.0,
                                                    "unit": "steps"}}
    after = _digest(root)
    assert {k: after[k] for k in before} == before, \
        "adding files changed an existing one"


def test_last_line_has_exactly_the_contract_keys():
    loaded = manifest.load_cell("opt-1.3b.train.seq2048")
    rec = {"loaded": loaded, "checks": {"ok": True},
           "compiles_in_window": 0, "attempted": 10, "failed": 0,
           "memory_peak_bytes": 123, "setup_s": 12.5,
           "steps": [{"t": 1.0 + i} for i in range(10)],
           "window": {"t0": 0.0, "t1": 10.0}, "global_batch": 4,
           "chips": 1,
           "device": {"platform": "tpu", "kind": "TPU v5 lite",
                      "count": 1}}
    line = result.assemble(rec, traced=False, rehearsal=False,
                           log=lambda *_: None)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["metrics"]["train.records_per_s_per_chip"] == {
        "value": 4.0, "unit": "records/s/chip"}
    assert line["metrics"]["setup_s"]["value"] == 12.5
    assert line["correct"] is True
    # a compilation inside the window makes the run incorrect
    rec["compiles_in_window"] = 1
    assert result.assemble(rec, traced=False, rehearsal=False,
                           log=lambda *_: None)["correct"] is False
