"""The served step's host-device boundary (ISSUE 28).

The decode state lives on the device: a decode dispatch returns it and the
next one takes it as it is; only a mirror the host wrote since goes up, as
a NumPy argument of the dispatch itself; one small download comes back and
the logits stay on the device. Oracles:

- a step after which nothing was admitted or released uploads nothing and
  transfers no logits;
- every writer of a mirror (admit, release, park and resume, the
  quarantine, ``override_tokens``) is seen by the next step: the streams
  equal a fresh engine's that serves each request alone;
- the base key made on the host is ``jax.random.PRNGKey``'s;
- ``last_logits`` still gives the step's logits, and is counted once;
- the round's path calls no eager device operation, and (ISSUE 30) reads
  nothing back between a decode dispatch and the next.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gym_tpu.models.nanogpt import GPT, GPTConfig
from gym_tpu.serve import engine as engine_mod
from gym_tpu.serve.engine import (InferenceEngine, SamplingParams,
                                  derive_base_key)
from gym_tpu.serve.scheduler import RequestStatus, Scheduler

# "default_pool": what every caller that names no cache gets (page 16, a
# window a slot); the others name a page size
KINDS = {"default_pool": dict(),
         "paged": dict(page_size=8),
         "paged_chunk3": dict(page_size=8, decode_chunk=3),
         "spec": dict(page_size=8, decode_chunk=2, spec_tokens=3)}
SP = dict(temperature=0.9, top_k=7)


@pytest.fixture(scope="module")
def setup():
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    model = GPT(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 8), np.int64), train=False)["params"]
    return cfg, model, params


def _engine(setup, kind, num_slots=3):
    cfg, _model, params = setup
    return InferenceEngine(params, cfg, num_slots=num_slots, **KINDS[kind])


def _prompt(n, seed, vocab=48):
    return np.random.default_rng(seed).integers(0, vocab, n)


def _request(i, max_new=14):
    return _prompt(5 + 3 * i, 100 + i), SamplingParams(
        max_new_tokens=max_new, seed=40 + i, **SP)


def _alone(setup, kind, prompt, sp):
    """The stream of one request served alone by a fresh engine."""
    eng = _engine(setup, kind)
    _slot, ev = eng.admit(prompt, sp)
    toks = [ev.token]
    while not ev.finished:
        evs = eng.step()
        toks.extend(e.token for e in evs)
        ev = evs[-1]
    return toks


def _mirrors_are_what_the_device_holds(eng):
    """Every entry of the state the host has not written since the last
    dispatch (a row that finished in it frees its pages)."""
    assert eng._dev
    for name, dev in eng._dev.items():
        if name not in eng._stale:
            np.testing.assert_array_equal(np.asarray(dev),
                                          eng._mirror(name), err_msg=name)


class Streams:
    """Routes an engine's events to the requests that own the slots."""

    def __init__(self, eng):
        self.eng, self.by_slot, self.toks, self.done = eng, {}, {}, set()

    def admit(self, name, prompt, sp):
        slot, ev = self.eng.admit(prompt, sp)
        self.by_slot[slot] = name
        self.toks[name] = [ev.token]
        return slot

    def step(self, **kw):
        events = self.eng.step(**kw)
        _mirrors_are_what_the_device_holds(self.eng)
        for ev in events:
            name = self.by_slot[ev.slot]      # KeyError: a slot nobody owns
            assert name not in self.done
            if not ev.poisoned:
                self.toks[name].append(ev.token)
            if ev.finished:
                self.done.add(name)
                del self.by_slot[ev.slot]
        for slot in {ev.slot for ev in events if ev.poisoned}:
            self.by_slot.pop(slot, None)      # evicted, finished or not
        return events

    def drain(self):
        for _ in range(200):
            if not self.by_slot:
                return
            self.step()
        raise AssertionError("engine did not drain")


# -- (a) a resident step ----------------------------------------------------


@pytest.mark.parametrize("kind", list(KINDS))
def test_step_after_no_admission_uploads_nothing(setup, kind):
    eng = _engine(setup, kind)
    for i in range(2):
        eng.admit(*_request(i, max_new=40))
    # ISSUE 30: the prefill writes the admitted row on the device, so not
    # even the first step after admissions is handed a mirror (ISSUE 28's
    # engine sent them all up here, once: ``first > 0``, no resident step)
    eng.step()
    first = eng.stats.upload_arrays
    assert first == 0 and eng.stats.resident_steps == 1
    logits_bytes = eng.num_slots * eng.config.vocab_size * 4
    for n in range(1, 4):
        read = eng.stats.readback_bytes
        assert eng.step()
        assert eng.stats.upload_arrays == first
        assert eng.stats.resident_steps == n + 1
        assert 0 < eng.stats.readback_bytes - read < min(logits_bytes,
                                                         64 * 1024)
    assert not eng._stale
    _mirrors_are_what_the_device_holds(eng)


# -- (b) every writer of a mirror is seen by the next step ------------------


def _admit_later(setup, kind, s):
    s.admit("a", *_request(0))
    for _ in range(3):
        s.step()
    s.admit("b", *_request(1))


def _release_then_admit_into_the_slot(setup, kind, s):
    s.admit("a", *_request(0))
    slot = s.admit("cancelled", *_request(1))
    for _ in range(2):
        s.step()
    s.eng.release(slot)
    del s.by_slot[slot], s.toks["cancelled"]
    s.step()                                     # the freed row stays quiet
    assert s.admit("c", *_request(2)) == slot


def _park_then_resume_into_another_slot(setup, kind, s):
    slot = s.admit("a", *_request(0))
    s.admit("b", *_request(1))
    for _ in range(2):
        s.step()
    parked = s.eng.park(slot)
    del s.by_slot[slot]
    s.step()
    assert s.admit("c", *_request(2)) == slot
    s.step()
    other = s.eng.resume(parked)
    assert other != slot
    s.by_slot[other] = "a"


def _quarantined_row(setup, kind, s):
    slot = s.admit("poisoned", *_request(0))
    s.admit("b", *_request(1))
    s.step()
    eng = s.eng
    page = int(eng._bt[slot, 0])
    eng._cache = jax.tree.map(lambda x: x.at[page].set(jnp.nan),
                              eng._cache)
    assert any(e.poisoned for e in s.step())
    assert eng.stats.quarantined == 1 and slot in eng.free_slots()
    del s.toks["poisoned"]
    s.step()                                     # the evicted row stays quiet
    assert s.admit("c", *_request(2)) == slot


WRITERS = [_admit_later, _release_then_admit_into_the_slot,
           _park_then_resume_into_another_slot, _quarantined_row]


@pytest.mark.parametrize("writer,kind", [
    pytest.param(w, k, id=f"{w.__name__.strip('_')}-{k}")
    for w in WRITERS for k in KINDS])
def test_writer_of_a_mirror_is_seen_by_the_next_step(setup, kind, writer):
    s = Streams(_engine(setup, kind))
    writer(setup, kind, s)
    s.drain()
    assert s.eng.stats.resident_steps > 0
    requests = {"a": _request(0), "b": _request(1), "c": _request(2)}
    for name, toks in s.toks.items():
        assert toks == _alone(setup, kind, *requests[name]), name


@pytest.mark.parametrize("kind", list(KINDS))
def test_override_tokens_are_seen_by_the_next_step(setup, kind):
    """A forced input token replaces what the device carried: an engine
    forced now and then gives the tokens of a fresh engine that is forced
    along the same inputs at every step, and at a forced step the logits
    of the model's plain forward over what was fed."""
    forced = {2: 7, 3: 11, 6: 5}
    eng = _engine(setup, kind)
    slot, ev = eng.admit(*_request(0, max_new=30))
    fed, out, logits = [], [], []
    tok = ev.token
    for n in range(9):
        if n in forced:
            tok = forced[n]
            evs = eng.step(override_tokens={slot: tok})
            logits.append(eng.last_logits[slot].copy())
        else:
            evs = eng.step()
            logits.append(None)
        fed.append(tok)
        out.append([e.token for e in evs])
        tok = evs[-1].token
    assert eng.stats.resident_steps > 0
    # the replay runs one token a step, so it is fed every token the first
    # engine fed or emitted, and must emit the same tokens
    inputs = []
    for tok, toks in zip(fed, out):
        inputs.extend([tok] + toks[:-1])
    replay = _engine(setup, kind)
    rslot, _ = replay.admit(*_request(0, max_new=30))
    got = [replay.step(override_tokens={rslot: tok})[0].token
           for tok in inputs]
    assert got == [t for toks in out for t in toks]
    # and a forced step's logits are the model's own at the forced input
    cfg, model, params = setup
    seq = list(_request(0)[0])
    for n, (tok, toks) in enumerate(zip(fed, out)):
        seq.append(tok)
        if logits[n] is not None:
            full = model.apply({"params": params}, np.asarray(seq)[None],
                               train=False)
            np.testing.assert_allclose(logits[n], np.asarray(full)[0, -1],
                                       rtol=1e-4, atol=1e-5)
        seq.extend(toks[:-1])


# -- (c) the base key -------------------------------------------------------


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("seed", [0, 1, 2 ** 31, 2 ** 32 - 1, 2 ** 32,
                                  2 ** 63 - 1, -1])
def test_host_base_key_is_prngkeys(seed, x64):
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.PRNGKey(seed))
        got = derive_base_key(seed)
    assert got.dtype == np.uint32 and got.shape == (2,)
    np.testing.assert_array_equal(got, want)


def test_host_base_key_refuses_what_prngkey_refuses():
    with pytest.raises(OverflowError):
        jax.random.PRNGKey(2 ** 63)
    with pytest.raises(OverflowError):
        derive_base_key(2 ** 63)


# -- (d) last_logits --------------------------------------------------------


@pytest.mark.parametrize("kind", list(KINDS))
def test_last_logits_are_fetched_on_demand_and_counted_once(setup, kind):
    cfg, model, params = setup
    eng = _engine(setup, kind)
    assert eng.last_logits is None
    prompt, sp = _request(0, max_new=20)
    slot, ev = eng.admit(prompt, sp)
    seq = list(prompt) + [ev.token]
    for _ in range(3):
        # one token a step: the logits are those at the fed token
        ev = eng.step(override_tokens={slot: seq[-1]})[0]
        before = eng.stats.readback_bytes
        got = eng.last_logits
        assert eng.stats.readback_bytes - before == got.nbytes \
            == eng.num_slots * cfg.vocab_size * 4
        assert eng.last_logits is got
        assert eng.stats.readback_bytes - before == got.nbytes
        full = model.apply({"params": params}, np.asarray(seq)[None],
                           train=False)
        np.testing.assert_allclose(got[slot], np.asarray(full)[0, -1],
                                   rtol=1e-4, atol=1e-5)
        seq.append(ev.token)
    eng.step()
    assert eng._logits_host is None          # a new step, nothing fetched


# -- (e) no eager device operation on the round's path ----------------------


class _Refusing:
    """``module`` with some of its callables replaced by a refusal."""

    def __init__(self, module, **refused):
        self._module, self._refused = module, refused

    def __getattr__(self, name):
        if name in self._refused:
            return self._refused[name]
        return getattr(self._module, name)


def _refuse(what):
    def refuse(*_a, **_k):
        raise AssertionError(f"eager device call on the round's path: "
                             f"{what}")
    return refuse


@pytest.mark.parametrize("kind", list(KINDS))
def test_served_round_issues_no_eager_device_operation(setup, kind,
                                                       monkeypatch):
    alone = _alone(setup, kind, *_request(2, max_new=6))
    eng = _engine(setup, kind, num_slots=2)
    sched = Scheduler(eng)
    monkeypatch.setattr(engine_mod, "jnp", _Refusing(
        jnp, asarray=_refuse("jnp.asarray"), array=_refuse("jnp.array")))
    monkeypatch.setattr(engine_mod, "jax", _Refusing(
        jax, random=_Refusing(jax.random,
                              PRNGKey=_refuse("jax.random.PRNGKey"),
                              key=_refuse("jax.random.key")),
        device_put=_refuse("jax.device_put")))
    first = [sched.submit(*_request(i, max_new=40)) for i in range(2)]
    for _ in range(4):                       # two admissions, then steps
        sched.step()
    assert all(r.status is RequestStatus.RUNNING for r in first)
    assert eng.stats.decode_steps >= 3 and eng.stats.resident_steps >= 2
    sched.cancel(first[0])
    sched.step()                             # the release
    assert len(eng.free_slots()) == 1
    again = sched.submit(*_request(2, max_new=6))
    for _ in range(40):
        sched.step()
        if again.status is RequestStatus.DONE:
            break
    assert again.result(timeout=1) == alone


@pytest.mark.parametrize("kind", list(KINDS))
def test_served_round_reads_nothing_between_a_dispatch_and_the_next(
        setup, kind, monkeypatch):
    """ISSUE 30: the driver thread never waits for the device before the
    device has its next decode step. Every read-back on the round's path
    is one ``jax.device_get``, made right after a decode dispatch and of
    an OLDER step than that one (or of first tokens only); an admission
    reads nothing; and the engine has no other way to wait (``np.asarray``
    of a device array would be one: the events are built from what
    ``device_get`` returned)."""
    alone = [_alone(setup, kind, *_request(i, max_new=12 + 5 * i))
             for i in range(4)]
    eng = _engine(setup, kind, num_slots=2)
    sched = Scheduler(eng)
    log = []

    def device_get(tree):
        flight = eng._flight
        # what is being read is not the step that was just dispatched
        younger = flight is not None and not any(
            leaf is mine for leaf in jax.tree.leaves(tree)
            for mine in jax.tree.leaves(flight.read))
        log.append(("read", younger))
        return jax.device_get(tree)

    monkeypatch.setattr(engine_mod, "jax", _Refusing(
        jax, device_get=device_get))
    for name in ("_decode_prog", "_spec_prog"):
        prog = getattr(eng, name)
        if prog is not None:
            monkeypatch.setattr(
                eng, name, lambda *a, _prog=prog: (
                    log.append(("dispatch", True)), _prog(*a))[1])
    admit = eng.admit_nowait
    monkeypatch.setattr(eng, "admit_nowait", lambda *a: (
        log.append(("admit", True)), admit(*a))[1])
    reqs = [sched.submit(*_request(i, max_new=12 + 5 * i)) for i in range(4)]
    rounds = 0
    while not all(r.status is RequestStatus.DONE for r in reqs):
        sched.step()
        rounds += 1
        assert rounds < 200
    assert [r.result(timeout=1) for r in reqs] == alone
    kinds = [k for k, _ in log]
    assert kinds.count("admit") == 4 and kinds.count("dispatch") >= 10
    reads = [i for i, k in enumerate(kinds) if k == "read"]
    # each read follows a decode dispatch at once, and is of an older step
    # (the one read at the very end waits out the step dispatched before
    # the read that said no row was left: there the device has no work)
    assert all(kinds[i - 1] == "dispatch" and log[i][1] for i in reads[:-1])
    assert kinds[reads[-1] - 1] == "read" and not log[reads[-1]][1]
    assert len(reads) == kinds.count("dispatch") + 1
    # all but the first dispatch, counted in scanned steps like the total
    assert (eng.stats.steps_ahead
            == eng.stats.decode_steps - eng.decode_chunk)
