"""``chip_smoke.py``'s contract, as far as a CPU can show it, and the
device-layer rules the chip bring-up set: a named device that cannot be had
is an error, the compile cache is placed from outside, the attention path
taken is visible, a utilization needs a peak, and a parent that holds the
chip spawns no worker that wants it.
"""

import json
import logging
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


class _FakeDevice:
    platform = "tpu"
    device_kind = "TPU v5 lite"


# -- (a) the last line ------------------------------------------------------


@pytest.mark.parametrize("ok,n", [(True, 1), (True, 4), (False, 1)])
def test_last_line_has_exactly_the_contract_keys(ok, n):
    line = chip_smoke.last_line(ok, [_FakeDevice()] * n)
    assert "\n" not in line
    obj = json.loads(line)
    assert obj == {"ok": ok, "device": {"platform": "tpu",
                                        "kind": "TPU v5 lite", "count": n}}
    assert list(obj) == ["ok", "device"]
    assert list(obj["device"]) == ["platform", "kind", "count"]


def test_last_line_without_any_device():
    assert json.loads(chip_smoke.last_line(False, [])) == {
        "ok": False, "device": {"platform": None, "kind": None, "count": 0}}


# -- (b) the script on a machine with no chip -------------------------------


def _run_smoke(args, cache_dir, cwd=REPO, script=SMOKE, xla_flags=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    # placed from outside, as on any machine: nothing lands in the tree
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    if xla_flags:
        env["XLA_FLAGS"] = xla_flags
    p = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=600)
    lines = p.stdout.splitlines()
    assert lines, p.stderr[-2000:]
    for line in lines:                       # stdout is JSON lines only
        json.loads(line)
    return p, lines


def _assert_failed_on_the_contracts_line(p, lines, count=1):
    assert p.returncode != 0
    assert p.stdout.endswith(lines[-1] + "\n")       # nothing after it
    assert json.loads(lines[-1]) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": count}}
    assert "Traceback" not in p.stdout


def test_smoke_without_a_chip_fails_at_once(tmp_path):
    p, lines = _run_smoke([], tmp_path)
    _assert_failed_on_the_contracts_line(p, lines)
    phases = [json.loads(x) for x in lines[:-1]]
    assert [x["phase"] for x in phases] == ["env"]   # no phase ran on a CPU
    assert phases[0]["checks"]["platform_is_tpu"] is False
    assert phases[0]["cache_dir"] == str(tmp_path)   # the variable wins


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """The driver also runs the script without the program beside it."""
    alone = shutil.copy(SMOKE, tmp_path)
    p, lines = _run_smoke([], tmp_path, cwd=str(tmp_path), script=alone)
    _assert_failed_on_the_contracts_line(p, lines)


def test_smoke_rehearsal_runs_every_phase_and_still_fails(tmp_path):
    """The CPU rehearsal of the one-chip run at tiny sizes: every phase
    of the control flow runs (train, train + checkpoint, serve from it,
    int8), the served streams equal ``generate_fast`` — and the verdict
    is still a failure, because a rehearsal is not a chip run."""
    p, lines = _run_smoke(["--rehearse"], tmp_path)
    _assert_failed_on_the_contracts_line(p, lines)
    phases = {x["phase"]: x for x in map(json.loads, lines[:-1])}
    assert list(phases) == ["env", "train_1node", "train_4fold_diloco",
                            "serve", "serve_int8", "wrap_up"]
    for name in ("train_1node", "train_4fold_diloco"):
        checks = phases[name]["checks"]
        assert checks.pop("pallas_kernel_in_step") is False   # dense on CPU
        assert all(checks.values()), (name, checks)
    for name in ("serve", "serve_int8", "wrap_up"):
        assert phases[name]["ok"], phases[name]
    assert phases["serve"]["checks"]["all_equal_generate_fast"]
    assert any(r["streamed"] for r in phases["serve"]["requests"])
    # off the TPU the paged attend is the gather path: the engine forced
    # along generate_fast's tokens samples them, its logits are the plain
    # forward's to rounding, and no kernel dispatch is counted
    engines = phases["serve"]["engines"]
    assert engines["paged_attend_path"] == "gather"
    assert engines["paged_stream_equal"] and engines["steps"] > 1
    assert engines["paged_logit_gap_max"] < 1e-4
    assert phases["serve"]["checks"]["paged_equals_generate_fast"]
    assert phases["serve"]["stats"]["paged_kernel_dispatches"] == 0
    assert phases["serve"]["streams_equal_generate_fast"] == len(
        phases["serve"]["requests"])
    assert phases["wrap_up"]["paged_attention_paths"] and all(
        "gather" in path
        for path in phases["wrap_up"]["paged_attention_paths"])
    assert phases["wrap_up"]["threads_alive"] == []
    assert all("dense" in path
               for path in phases["wrap_up"]["attention_paths"])


def test_smoke_four_chip_rehearsal_on_forced_host_devices(tmp_path):
    """``--chips 4`` on four forced host devices: only that path and its
    one-device fold run, the state sits on four devices, the step holds
    cross-device collectives, and the two loss curves agree."""
    p, lines = _run_smoke(
        ["--rehearse", "--chips", "4"], tmp_path,
        xla_flags="--xla_force_host_platform_device_count=4")
    _assert_failed_on_the_contracts_line(p, lines, count=4)
    phases = {x["phase"]: x for x in map(json.loads, lines[:-1])}
    assert list(phases) == ["env", "four_chips_diloco",
                            "four_chips_allreduce", "wrap_up"]
    for name in ("four_chips_diloco", "four_chips_allreduce"):
        ph = phases[name]
        assert ph["checks"]["state_on_4_devices"], ph["devices_holding"]
        assert ph["checks"]["collectives_in_step"]
        assert ph["checks"]["losses_agree"], ph["max_rel_loss_deviation"]
        assert ph["folded_on_one"]["cross_device_collectives_in_step"] == 0


# -- (c) a named device that cannot be had is an error ----------------------


def test_resolve_devices_named_tpu_raises_on_a_cpu_host():
    from gym_tpu.trainer import _resolve_devices
    with pytest.raises(RuntimeError):
        _resolve_devices("tpu", None)
    assert _resolve_devices("cpu", [0]) == [jax.devices("cpu")[0]]
    assert _resolve_devices(None, None) == jax.devices()


# -- (d) the compile cache is placed from outside ---------------------------


def test_cache_dir_resolution(monkeypatch, tmp_path):
    from gym_tpu.programs import registry
    monkeypatch.setattr(registry, "_LISTENER_INSTALLED", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert registry.resolve_cache_dir() == str(tmp_path / "env")
    assert registry.resolve_cache_dir("arg") == str(tmp_path / "env")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert registry.resolve_cache_dir("arg") == "arg"
    assert registry.resolve_cache_dir() == os.path.join(REPO, ".jax_cache")


def test_cache_dir_already_enabled_beats_the_default(monkeypatch):
    """A later default call (``fit()`` with no argument) must not move a
    cache the program already placed."""
    from gym_tpu.programs import registry
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(registry, "_LISTENER_INSTALLED", True)
    placed = jax.config.jax_compilation_cache_dir
    assert placed and registry.resolve_cache_dir() == placed
    assert registry.resolve_cache_dir("arg") == "arg"


def test_new_cache_directory_is_ignored_by_git():
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = f.read().split()
    assert "/.jax_cache/" in ignored and "/chiprun_out/" in ignored


# -- the attention path taken is visible ------------------------------------


def test_flash_entry_logs_the_dense_path_off_tpu(caplog):
    from gym_tpu.ops.flash_attention import flash_causal_attention
    q = jnp.zeros((1, 3, 128, 8), jnp.float32)       # a shape no test shares
    with caplog.at_level(logging.INFO, "gym_tpu.ops.flash_attention"):
        flash_causal_attention(q, q, q)
        flash_causal_attention(q, q, q)              # logged once per shape
    assert [r.getMessage() for r in caplog.records] == [
        "attention path dense for q(1, 3, 128, 8) float32"]


# -- a utilization needs a peak ---------------------------------------------


def test_mfu_is_none_where_the_device_has_no_peak(tmp_path):
    from gym_tpu import Trainer
    from gym_tpu.data import ArrayDataset
    from gym_tpu.models.nanogpt import (GPT, GPTConfig, PEAK_BF16_FLOPS,
                                        device_peak_flops)
    from gym_tpu.strategy.optim import OptimSpec
    from gym_tpu.strategy.simple_reduce import SimpleReduceStrategy

    assert PEAK_BF16_FLOPS == {"TPU v5 lite": 197e12}
    assert device_peak_flops(_FakeDevice()) == 197e12
    with pytest.raises(KeyError, match="cpu"):
        device_peak_flops()
    toks = np.random.default_rng(0).integers(0, 32, (16, 17))
    res = Trainer(GPT(GPTConfig(block_size=16, vocab_size=32, n_layer=1,
                                n_head=1, n_embd=16)),
                  ArrayDataset(toks[:, :-1], toks[:, 1:])).fit(
        strategy=SimpleReduceStrategy(OptimSpec("adamw", lr=1e-3)),
        num_nodes=1, max_steps=2, batch_size=4, val_size=0, val_interval=0,
        show_progress=False, log_dir=str(tmp_path))
    assert res.mfu is None and np.isfinite(res.final_train_loss)


# -- one process for each chip ----------------------------------------------


@pytest.mark.parametrize("worker_env,refused", [
    (None, True), ({"JAX_PLATFORMS": "tpu,cpu"}, True),
    ({"JAX_PLATFORMS": "cpu"}, False)])
def test_process_fleet_refuses_when_the_parent_holds_the_tpu(
        monkeypatch, tmp_path, worker_env, refused):
    """``--out-of-process`` from a parent whose params sit on a TPU, with
    workers not pinned elsewhere: refused before anything is spawned."""
    from gym_tpu.models.nanogpt import GPTConfig
    from gym_tpu.serve import router
    monkeypatch.setattr(router, "_params_on_tpu", lambda params: True)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    kw = dict(params={"w": np.zeros(2, np.float32)}, config=GPTConfig(),
              device=None, env=worker_env)
    if refused:
        with pytest.raises(router.ChipHeldByParentError, match="one|chip"):
            router.WorkerSpawner(str(tmp_path), **kw)
        assert os.listdir(tmp_path) == []
    else:
        router.WorkerSpawner(str(tmp_path), **kw)
        assert "params.pkl" in os.listdir(tmp_path)
