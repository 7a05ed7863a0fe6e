"""SDC defense (ISSUE 20): crc32c, checkpoint sidecars, wire crc,
corruption fault actions, quarantine collisions, watchdog attribution.

The layer-by-layer detection story: wrong bytes on disk are caught by
the checkpoint sidecar (``ChecksumMismatchError`` → quarantine), wrong
bytes on the wire by the per-frame crc (``FrameCorruptError`` →
failover), and wrong values in live device state by the training guard
(``test_guard_rollback.py``). Each detector is pinned here against its
matching injected fault."""

import json
import os
import shutil
import threading
import time

import numpy as np
import pytest

import jax

from gym_tpu.utils import integrity
from gym_tpu.utils.checkpoint import CheckpointManager, restore_params
from gym_tpu.utils.integrity import (ChecksumMismatchError, crc32c,
                                     crc32c_reference,
                                     tree_fingerprint,
                                     tree_fingerprint_host,
                                     verify_sidecar, write_sidecar)
from gym_tpu.utils.resilience import (FAULT_SITES, FaultRegistry,
                                      corrupt_point, dump_thread_stacks,
                                      faults)
from gym_tpu.serve import wire


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# -- crc32c ----------------------------------------------------------------


# both implementations: the one in use (C where installed) and the
# pure-Python reference it must equal
_CRCS = pytest.mark.parametrize("crc", [crc32c, crc32c_reference])


@_CRCS
def test_crc32c_reference_vector(crc):
    # the canonical Castagnoli check value (RFC 3720 B.4)
    assert crc(b"123456789") == 0xE3069283
    assert crc(b"") == 0
    # chaining == one-shot (streamed file hashing depends on it)
    data = bytes(range(256)) * 41  # deliberately not 8-aligned
    assert crc(data) == crc(data[100:], crc(data[:100]))


@_CRCS
def test_crc32c_detects_single_bitflip(crc):
    data = os.urandom(4096)
    ref = crc(data)
    flipped = bytearray(data)
    flipped[1234] ^= 0x10
    assert crc(bytes(flipped)) != ref


def test_crc32c_in_use_equals_the_reference():
    """Whatever computes the sidecars' crc32c (the C implementation
    where it is installed) must agree with the reference, one-shot and
    chained, on bytes and on other buffers."""
    data = os.urandom(50_001)
    want = crc32c_reference(data)
    assert crc32c(data) == want
    assert crc32c(memoryview(data)) == crc32c(bytearray(data)) == want
    assert crc32c(data[777:], crc32c(data[:777])) == want


# -- checkpoint sidecars ---------------------------------------------------


def _make_step_dir(tmp_path, name="7"):
    d = tmp_path / name
    (d / "state").mkdir(parents=True)
    (d / "state" / "shard0").write_bytes(os.urandom(2048))
    (d / "meta.json").write_text('{"k": 1}')
    return str(d)


def test_sidecar_roundtrip_and_mismatch(tmp_path):
    d = _make_step_dir(tmp_path)
    write_sidecar(d, fingerprint={"sum": 1.5, "num_leaves": 3})
    assert verify_sidecar(d) is True
    rec = json.loads(open(os.path.join(d, "integrity.json")).read())
    assert rec["algo"] == "crc32c"
    assert "state/shard0" in rec["files"]
    assert rec["fingerprint"]["num_leaves"] == 3
    # the sidecar never hashes itself
    assert "integrity.json" not in rec["files"]
    # flip one byte in the shard → typed mismatch naming the file
    p = os.path.join(d, "state", "shard0")
    raw = bytearray(open(p, "rb").read())
    raw[100] ^= 0x1
    open(p, "wb").write(bytes(raw))
    with pytest.raises(ChecksumMismatchError, match="state/shard0"):
        verify_sidecar(d)


def test_sidecar_missing_file_and_old_format(tmp_path):
    d = _make_step_dir(tmp_path)
    # no sidecar at all = pre-integrity checkpoint: accepted, returns
    # False (soft-degrade — old checkpoints must keep restoring)
    assert verify_sidecar(d) is False
    write_sidecar(d)
    os.remove(os.path.join(d, "meta.json"))
    with pytest.raises(ChecksumMismatchError, match="file missing"):
        verify_sidecar(d)


def test_tree_fingerprint_host_and_device_agree():
    tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": {"c": np.ones(5, dtype=np.int32)},
            "skip": "not-an-array"}
    host = tree_fingerprint_host(tree)
    assert host["num_leaves"] == 2
    dev = float(np.asarray(jax.jit(tree_fingerprint)(
        {"a": tree["a"], "b": tree["b"]})))
    assert abs(dev - host["sum"]) < 1e-3


# -- corruption fault actions ----------------------------------------------


def test_spec_grammar_parses_bitflip_and_truncate():
    reg = FaultRegistry()
    reg.configure("checkpoint.bytes:bitflip=3@2,wire.frame:truncate@1-4,"
                  "dispatch.state:bitflip=1@5+")
    rules = reg._rules
    assert [(r.site, r.action, r.arg, r.first, r.last) for r in rules] == [
        ("checkpoint.bytes", "bitflip", 3.0, 2, 2),
        ("wire.frame", "truncate", 0.0, 1, 4),
        ("dispatch.state", "bitflip", 1.0, 5, None),
    ]
    with pytest.raises(ValueError, match="unknown fault action"):
        reg.install("wire.frame", "scramble")
    for site in ("checkpoint.bytes", "wire.frame", "dispatch.state"):
        assert site in FAULT_SITES


def test_corrupt_is_deterministic_and_windowed():
    reg = FaultRegistry()
    reg.configure("wire.frame:bitflip=2@2")
    data = bytes(range(200))
    assert reg.corrupt("wire.frame", data) == data        # hit 1: clean
    hit2 = reg.corrupt("wire.frame", data)                # hit 2: armed
    assert hit2 != data and len(hit2) == len(data)
    assert reg.corrupt("wire.frame", data) == data        # hit 3: clean
    # same (site, hit) → same wrong bytes: campaigns reproduce exactly
    reg2 = FaultRegistry()
    reg2.configure("wire.frame:bitflip=2@2")
    reg2.corrupt("wire.frame", data)
    assert reg2.corrupt("wire.frame", data) == hit2
    assert reg.hits("wire.frame") == 3


def test_truncate_action_and_corrupt_point_gating():
    reg = FaultRegistry()
    reg.configure("checkpoint.bytes:truncate=10")
    out = reg.corrupt("checkpoint.bytes", bytes(100))
    assert len(out) == 90
    reg.reset()
    reg.configure("checkpoint.bytes:truncate")  # default: half
    assert len(reg.corrupt("checkpoint.bytes", bytes(100))) == 50
    # module-level corrupt_point: inert (not even a hit) when unarmed
    data = b"payload"
    assert corrupt_point("wire.frame", data) is data
    assert faults.hits("wire.frame") == 0


def test_corruption_actions_inert_at_plain_fault_points():
    # a bitflip armed at a non-payload site must not crash fire()
    reg = FaultRegistry()
    reg.configure("dispatch.boundary:bitflip=1")
    reg.fire("dispatch.boundary")
    assert reg.hits("dispatch.boundary") == 1


# -- wire frame crc --------------------------------------------------------


def test_wire_frames_carry_and_strip_crc():
    frame = {"type": "chunk", "id": 11, "tokens": [5, 6, 7]}
    payload = wire.encode_frame(frame)[4:]
    raw = json.loads(payload)
    assert "crc" in raw and len(raw["crc"]) == 8
    # verified then STRIPPED: handlers never see the field
    assert wire.decode_payload(payload) == frame


def test_wire_crc_detects_content_corruption():
    frame = {"type": "chunk", "id": 11, "tokens": [5, 6, 7]}
    payload = bytearray(wire.encode_frame(frame)[4:])
    # corrupt a token digit so the JSON stays VALID — only the crc can
    # catch this one (the silent wrong-token case)
    idx = payload.index(b"5")
    payload[idx : idx + 1] = b"9"
    with pytest.raises(wire.FrameCorruptError, match="crc mismatch"):
        wire.decode_payload(bytes(payload))
    # FrameCorruptError IS a WireError: the router's mark-dead/failover
    # path handles it with zero special-casing
    assert issubclass(wire.FrameCorruptError, wire.WireError)


def test_wire_old_format_frames_accepted_unverified():
    frame = {"type": "done", "id": 3, "tokens_total": 9, "ttft_s": 0.1}
    old = json.dumps(frame, separators=(",", ":")).encode()
    assert wire.decode_payload(old) == frame


def test_wire_frame_fault_site_fires_in_encode():
    faults.install("wire.frame", "bitflip", arg=1, first=1, last=1)
    frame = {"type": "chunk", "id": 1, "tokens": [1, 2, 3]}
    corrupted = wire.encode_frame(frame)
    with pytest.raises(wire.WireError):
        wire.decode_payload(corrupted[4:])
    faults.reset()
    clean = wire.encode_frame(frame)
    assert wire.decode_payload(clean[4:]) == frame


def test_wire_truncate_fault_yields_typed_error():
    faults.install("wire.frame", "truncate", first=1, last=1)
    corrupted = wire.encode_frame({"type": "chunk", "id": 1,
                                   "tokens": [1, 2, 3]})
    # framing is intact (length prefix matches the truncated payload)
    # so the CONTENT layer must reject it
    (length,) = wire._LEN.unpack(corrupted[:4])
    assert length == len(corrupted) - 4
    with pytest.raises(wire.WireError):
        wire.decode_payload(corrupted[4:])


# -- quarantine suffix collisions ------------------------------------------


def test_double_quarantine_takes_next_suffix(tmp_path):
    mgr = CheckpointManager(str(tmp_path), "run", async_save=False)
    try:
        step = os.path.join(mgr.directory, "7")
        os.makedirs(os.path.join(step, "state"))
        # a PREVIOUS quarantine of the same step already holds -0
        os.makedirs(step + ".corrupt-0")
        mgr._quarantine_step(7)
        assert not os.path.exists(step)
        assert os.path.isdir(step + ".corrupt-1")
        assert os.path.isdir(step + ".corrupt-0")  # untouched
        # and a third round lands on -2
        os.makedirs(os.path.join(step, "state"))
        mgr._quarantine_step(7)
        assert os.path.isdir(step + ".corrupt-2")
    finally:
        mgr.close()


# -- end-to-end: corrupt checkpoint detected at restore --------------------


class _TinyLossModel:
    pass


def _fit_tiny(base, max_steps, resume="auto", **kw):
    import flax.linen as nn
    import jax.numpy as jnp
    import optax

    from gym_tpu import Trainer
    from gym_tpu.data import ArrayDataset
    from gym_tpu.strategy import OptimSpec, SimpleReduceStrategy

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, batch, train=True):
            x, y = batch
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(16)(x))
            return optax.softmax_cross_entropy_with_integer_labels(
                nn.Dense(10)(x).astype(jnp.float32), y).mean()

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, size=128).astype(np.int32)
    x = rng.normal(0, 0.3, size=(128, 8, 8)).astype(np.float32)
    for i, y in enumerate(labels):
        x[i, y % 8, :] += 1.5
    return Trainer(Tiny(), ArrayDataset(x, labels)).fit(
        strategy=SimpleReduceStrategy(OptimSpec("sgd", lr=0.05)),
        num_nodes=2, max_steps=max_steps, batch_size=16, minibatch_size=8,
        val_interval=0, show_progress=False, seed=3,
        checkpoint_interval=3, save_dir=os.path.join(base, "ckpt"),
        run_name="sdc", log_dir=os.path.join(base, "logs"),
        async_checkpoint=False, prefetch=False, resume=resume, **kw)


def test_corrupt_checkpoint_quarantined_at_restore(tmp_path):
    """The tentpole disk story end-to-end: every save writes a sidecar;
    an injected bitflip in the newest step is DETECTED at restore,
    quarantined through ``.corrupt-k``, and the run resumes from the
    older verified step — never restoring wrong bytes."""
    base = str(tmp_path)
    _fit_tiny(base, 6)
    run_dir = os.path.join(base, "ckpt", "sdc")
    assert os.path.exists(os.path.join(run_dir, "6", "integrity.json"))
    faults.install("checkpoint.bytes", "bitflip", arg=3)
    integrity.corrupt_checkpoint_files(os.path.join(run_dir, "6"))
    faults.reset()
    res = _fit_tiny(base, 9)
    assert res.steps == 9
    names = os.listdir(run_dir)
    assert any(n.startswith("6.corrupt-") for n in names), names
    # the corrupt step was re-saved cleanly on the way to 9
    assert verify_sidecar(os.path.join(run_dir, "9")) is True


def test_restore_params_skips_corrupt_newest(tmp_path):
    base = str(tmp_path)
    _fit_tiny(base, 6)
    run_dir = os.path.join(base, "ckpt", "sdc")
    faults.install("checkpoint.bytes", "bitflip", arg=2)
    integrity.corrupt_checkpoint_files(os.path.join(run_dir, "6"))
    faults.reset()
    step, params, _extra = restore_params(run_dir)
    assert step == 3  # fell back past the corrupt newest, READ-ONLY
    assert os.path.isdir(os.path.join(run_dir, "6"))  # not quarantined
    assert params


def test_checkpoint_bytes_fault_fires_during_save(tmp_path):
    """Arming checkpoint.bytes during the run corrupts the bytes AFTER
    the sidecar records the good ones — the write-path integration the
    chaos campaigns rely on."""
    base = str(tmp_path)
    faults.install("checkpoint.bytes", "bitflip", arg=2, first=2, last=2)
    try:
        _fit_tiny(base, 6)
    finally:
        faults.reset()
    run_dir = os.path.join(base, "ckpt", "sdc")
    assert verify_sidecar(os.path.join(run_dir, "3")) is True
    with pytest.raises(ChecksumMismatchError):
        verify_sidecar(os.path.join(run_dir, "6"))


# -- watchdog names the in-flight program ----------------------------------


def test_watchdog_dump_names_inflight_program():
    from gym_tpu.programs.registry import (ProgramRegistry,
                                           inflight_programs)

    reg = ProgramRegistry()
    release = threading.Event()
    entered = threading.Event()

    def slow_fn(x):
        entered.set()
        release.wait(10.0)
        return x

    wrapped = reg.track_jit("train_step[tiny]", {"lr": 0.1}, (), slow_fn)
    t = threading.Thread(target=wrapped, args=(np.zeros(3),), daemon=True)
    t.start()
    try:
        assert entered.wait(10.0)
        # the dump a hung run leaves behind attributes the wedged
        # dispatch to the registry key, not just "inside jax"
        dump = dump_thread_stacks("watchdog: test dump")
        assert "in-flight registry programs" in dump
        assert "train_step[tiny]" in dump
        assert t.ident in inflight_programs()
    finally:
        release.set()
        t.join(5.0)
    assert t.ident not in inflight_programs()  # cleared on exit


def test_dump_without_inflight_has_no_program_section():
    dump = dump_thread_stacks("hdr")
    assert "in-flight registry programs" not in dump
