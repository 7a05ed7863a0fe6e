"""The harness end to end on the CPU: the last stdout line, refusals, and
that new files are found by name with no edit to an existing one."""

import json
import os
import shutil

import pytest

from conftest import ROOT, last_json
from perfbench import harness

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
CELLS = [("gpt2-base.train-node1", 1), ("gpt2-base.train-fold4-diloco", 1),
         ("gpt2-base.serve-closed", 1),
         ("gpt2-medium.train-spread4-allreduce", 4)]


@pytest.mark.parametrize("cell,devices", CELLS)
def test_rehearsal_of_every_cell_ends_on_the_contracts_line(run, cell,
                                                             devices):
    code, lines, err = run(["--workload", cell, "--seed", "3000000021",
                            "--seconds", "1.5", "--trace", "0",
                            "--rehearse"], devices=devices)
    assert code == 0, err[-2000:]
    line = last_json(lines)
    assert KEYS <= set(line) and DEVICE_KEYS <= set(line["device"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["count"] == devices
    # a rehearsal says what it is and prints no device metric
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert "setup_s" in line["metric_names"]
    assert any(json.loads(ln).get("rehearsal") for ln in lines[:2])
    # every number compared is printed beside its limit
    compared = [json.loads(ln) for ln in lines if '"compared"' in ln]
    assert compared and all({"value", "limit", "ok"} <= set(c)
                            for c in compared)


def test_traced_rehearsal_reads_the_per_layer_metrics(run):
    code, lines, err = run(["--workload", "gpt2-base.serve-closed",
                            "--seed", "5", "--seconds", "2", "--trace", "1",
                            "--rehearse"])
    assert code == 0, err[-2000:]
    names = last_json(lines)["metric_names"]
    for want in ("serve_round_ms_p50", "decode_batch_occupancy_pct",
                 "kv_pool_fill_pct", "closed_ttft_p50_ms",
                 "xla_compiles_in_window"):
        assert want in names


def test_no_accelerator_no_result(run):
    code, lines, _ = run(["--workload", "gpt2-base.train-node1", "--seed",
                          "1", "--seconds", "1", "--trace", "0"])
    assert code != 0
    assert not any(ln.startswith('{"correct"') for ln in lines)


def test_fewer_chips_than_the_cell_asks_for_no_result(run):
    code, lines, _ = run(["--workload",
                          "gpt2-medium.train-spread4-allreduce", "--seed",
                          "1", "--seconds", "1", "--trace", "0",
                          "--rehearse"], devices=2)
    assert code != 0
    assert not any(ln.startswith('{"correct"') for ln in lines)


def test_alone_in_a_directory_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the program under
    test is missing, so there is nothing to measure."""
    import subprocess
    import sys
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "gpt2-base.train-node1", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse"], cwd=tmp_path, capture_output=True,
        text=True, env=dict(os.environ, JAX_PLATFORMS="cpu",
                            PYTHONPATH=""), timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_a_failure_after_the_device_is_found_still_ends_on_the_line(run):
    code, lines, _ = run(
        ["--workload", "gpt2-base.train-node1", "--seed", "1", "--seconds",
         "1", "--trace", "0", "--rehearse"],
        patch="import gym_tpu.trainer as t\n"
              "def boom(*a, **k): raise RuntimeError('fit broke')\n"
              "t.Trainer.fit = boom\n")
    assert code != 0
    line = last_json(lines)
    assert set(line) == KEYS and line["correct"] is False
    assert line["metrics"] == {} and DEVICE_KEYS <= set(line["device"])


def test_new_files_are_found_by_name_with_no_edit(tmp_path):
    """A later PR adds a configuration, a traffic mix, a per-layer metric
    and a cell by adding files and entries."""
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _d, files in os.walk(tmp_path / "perfbench")
              for p in files}
    pb = tmp_path / "perfbench"
    cfg = harness.load_json(pb / "configs" / "gpt2-base.json")
    cfg["n_layer"] = 6
    (pb / "configs" / "gpt2-half.json").write_text(json.dumps(cfg))
    mix = harness.load_json(pb / "traffic" / "train-node1.json")
    mix["batch_size"] = 8
    (pb / "traffic" / "train-node1-b8.json").write_text(json.dumps(mix))
    (pb / "layer_metrics" / "tokens_per_step.py").write_text(
        "def read(facts):\n    return facts.get('tokens_per_step')\n")
    (pb / "layer_metrics" / "broken_reader.py").write_text(
        "def read(facts):\n    return facts['not there']\n")
    lim = harness.load_json(pb / "limits" / "gpt2-base.train-node1.json")
    lim["limits"]["loss_gap"] = 0.5
    (pb / "limits" / "gpt2-half.train-node1-b8.json").write_text(
        json.dumps(lim))
    bench["configs"].append({"name": "gpt2-half", "source": "test",
                             "file": "perfbench/configs/gpt2-half.json",
                             "reduced": ["n_layer"], "why": "test"})
    bench["workloads"].append({"name": "gpt2-half.train-node1-b8",
                               "config": "gpt2-half",
                               "traffic": "train-node1-b8", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "tokens_per_step", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "Step program",
        "moves": "train_tokens_per_s",
        "workloads": ["gpt2-half.train-node1-b8"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("gpt2-half.train-node1-b8")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = harness.load_cell("gpt2-half.train-node1-b8", root=str(tmp_path))
    assert spec["config"]["n_layer"] == 6
    assert spec["traffic"]["batch_size"] == 8
    assert spec["limits"]["limits"]["loss_gap"] == 0.5
    assert [m["name"] for m in spec["per_layer"]] == ["tokens_per_step"]
    assert {m["name"] for m in spec["end_to_end"]} == {
        "train_tokens_per_s", "setup_s"}
    got = harness.read_layer_metrics(spec, {"tokens_per_step": 8192})
    assert got == {"tokens_per_step": {"value": 8192.0, "unit": "tokens"}}
    # a reader that finds nothing to read is left out of the line
    assert harness.read_layer_metrics(spec, {}) == {}
    # and one that raises fails the traced run: its metric does not
    # silently vanish
    spec["per_layer"].append({"name": "broken_reader", "unit": "x"})
    with pytest.raises(KeyError):
        harness.read_layer_metrics(spec, {"tokens_per_step": 1})
    # and no file that was there has changed
    for dp, _d, files in os.walk(pb):
        for p in files:
            if p in before:
                assert open(os.path.join(dp, p), "rb").read() == before[p]


def test_benchmark_json_names_files_that_exist():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "limits", w["name"] + ".json"))
        assert len(w["why"]) <= 200
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "layer_metrics", m["name"] + ".py"))
        assert m["moves"] in e2e and set(m["workloads"]) <= names
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_dropout_comes_from_the_configuration_file():
    """``GPTConfig`` gets the rate the configuration file states, not one
    fixed in code; three published rates that disagree are refused."""
    from perfbench import weights
    from perfbench.kinds import fit
    sizes = harness.load_json(os.path.join(ROOT, "perfbench", "configs",
                                           "gpt2-base.json"))
    mix = harness.load_json(os.path.join(ROOT, "perfbench", "traffic",
                                         "train-node1.json"))
    assert fit.gpt_config(sizes, mix).dropout == sizes["resid_pdrop"]
    assert fit.gpt_config({**sizes, "resid_pdrop": 0.1, "embd_pdrop": 0.1,
                           "attn_pdrop": 0.1}, mix).dropout == 0.1
    with pytest.raises(ValueError):
        weights.dropout_rate({**sizes, "attn_pdrop": 0.1})
