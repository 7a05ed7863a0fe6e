"""A decode step always in flight (ISSUE 30).

``Scheduler.step`` gives the device its next decode step before it reads
the one before it (``InferenceEngine.step(ahead=True)``): the prefill
program writes the admitted row into the decode state on the device, its
first token is read with the next step's download, and only a host write
to a live row waits the step in flight out. One case a hazard:

- steady state is one step ahead and uploads nothing, admissions or not;
- a slot the host freed (cancel) under a step in flight and refilled at
  once never gets the old occupant's token;
- when load stops the last tokens are delivered and nothing stays in
  flight;
- park / resume under a step in flight continues byte-identically;
- a poisoned row is stopped by the program itself, quarantined alone, and
  its pages are written over before they are reused;
- a failover discards the dead engine's step in flight;
- plain, chunked and speculative engines under staggered admissions give
  the streams of requests served one at a time.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gym_tpu.models.nanogpt import GPT, GPTConfig
from gym_tpu.serve.engine import InferenceEngine, SamplingParams
from gym_tpu.serve.scheduler import (EngineFailedError, RequestCancelledError,
                                     RequestStatus, Scheduler,
                                     SlotQuarantinedError)

KINDS = {"plain": dict(page_size=8),
         "chunk4": dict(page_size=8, decode_chunk=4),
         "spec4": dict(page_size=8, spec_tokens=4)}
SP = dict(temperature=0.9, top_k=7)


@pytest.fixture(scope="module")
def setup():
    cfg = GPTConfig(block_size=64, vocab_size=48, n_layer=2, n_head=2,
                    n_embd=32, dropout=0.0, bias=True)
    params = GPT(cfg).init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 8), np.int64), train=False)["params"]
    return cfg, params


def _engine(setup, kind="plain", num_slots=2):
    cfg, params = setup
    return InferenceEngine(params, cfg, num_slots=num_slots, **KINDS[kind])


def _request(i, max_new=14):
    prompt = np.random.default_rng(100 + i).integers(0, 48, 5 + 3 * i)
    return prompt, SamplingParams(max_new_tokens=max_new, seed=40 + i, **SP)


_ALONE = {}


def _alone(setup, kind, i, max_new=14):
    """Request ``i``'s stream from a fresh engine that serves it alone,
    every step waited for."""
    if (kind, i, max_new) not in _ALONE:
        eng = _engine(setup, kind)
        slot, ev = eng.admit(*_request(i, max_new))
        toks = [ev.token]
        while not ev.finished:
            evs = eng.step()
            toks.extend(e.token for e in evs)
            ev = evs[-1]
        assert eng._flight is None and not eng._held
        _ALONE[kind, i, max_new] = toks
    return _ALONE[kind, i, max_new]


def _run(sched, reqs, limit=400):
    for _ in range(limit):
        if all(r.status in (RequestStatus.DONE, RequestStatus.FAILED)
               for r in reqs):
            return
        sched.step()
    raise AssertionError("scheduler did not finish")


def _idle(eng):
    return eng._flight is None and not eng._firsts and not eng._held


# -- steady state -----------------------------------------------------------


def test_steady_state_is_one_step_ahead_and_uploads_nothing(setup):
    """Six requests of different lengths through three slots: admissions
    fall between decode steps all along, and still every step but the
    first is dispatched before the one before it was read, none is handed
    a host array, and the only wait beyond a read is the one at the end
    (the read that says no row is left waits out the step after it)."""
    eng = _engine(setup, num_slots=3)
    sched = Scheduler(eng)
    lengths = [12, 20, 28, 9, 15, 11]
    reqs = [sched.submit(*_request(i, n)) for i, n in enumerate(lengths)]
    _run(sched, reqs)
    for i, (req, n) in enumerate(zip(reqs, lengths)):
        assert req.result(timeout=1) == _alone(setup, "plain", i, n)
    st = eng.stats
    assert st.prefills == 6 and st.decode_steps > 20
    assert st.steps_ahead == st.decode_steps - 1
    assert st.resident_steps == st.decode_steps
    assert st.upload_arrays == 0 and st.drains == 1
    assert sched.inflight() == 0 and _idle(eng)


def test_first_token_is_an_event_of_the_next_read(setup):
    """``admit_nowait`` returns before anything was read; the token comes
    with the next read, after the step's own tokens, as the slot's first
    event."""
    eng = _engine(setup)
    a = eng.admit_nowait(*_request(0))
    assert eng._firsts and eng._active[a] and not eng._held
    first, = eng.step(ahead=True)            # step 1 queued, then the read
    assert (first.slot, first.token) == (a, _alone(setup, "plain", 0)[0])
    b = eng.admit_nowait(*_request(1))       # behind step 1, before step 2
    evs = eng.step(ahead=True)
    assert [(e.slot, e.token) for e in evs] == [
        (a, _alone(setup, "plain", 0)[1]), (b, _alone(setup, "plain", 1)[0])]
    assert eng._flight is not None and eng._flight.ahead
    assert eng.stats.upload_arrays == 0


# -- (a) a slot the host freed under a step in flight -----------------------


def test_cancelled_slot_refilled_at_once_gets_no_old_token(setup):
    eng = _engine(setup)
    sched = Scheduler(eng)
    keep = sched.submit(*_request(0, 30))
    gone = sched.submit(*_request(1, 30))
    for _ in range(5):
        sched.step()
    assert eng._flight is not None           # a step that saw `gone` active
    slot = next(s for s, r in sched._by_slot.items() if r is gone)
    had = list(gone.tokens)
    sched.cancel(gone)
    new = sched.submit(*_request(2, 12))
    sched.step()                             # the sweep: release, drain
    assert gone.status is RequestStatus.FAILED
    assert isinstance(gone.exception, RequestCancelledError)
    assert eng.stats.drains == 1 and slot in eng.free_slots()
    sched.step()                             # refilled in the next round
    assert sched._by_slot[slot] is new
    _run(sched, [keep, new])
    assert new.result(timeout=1) == _alone(setup, "plain", 2, 12)
    assert keep.result(timeout=1) == _alone(setup, "plain", 0, 30)
    # the cancelled stream is a prefix of its own, a round's tokens at most
    # past what the client had seen
    assert gone.tokens[:len(had)] == had
    assert gone.tokens == _alone(setup, "plain", 1, 30)[:len(gone.tokens)]
    assert eng.stats.upload_arrays == 1      # the `active` mirror, once


def test_held_events_keep_their_slot_out_of_free_slots(setup):
    """A host write waits the step in flight out and keeps its events: a
    slot whose last token is among them is not free before they are
    handed out."""
    eng = _engine(setup)
    a, _ = eng.admit(*_request(0, 3))        # two decode steps to live
    b, _ = eng.admit(*_request(1, 30))
    eng.step(ahead=True)                     # step 1 in flight
    eng.step(ahead=True)                     # step 2 in flight, 1 read
    eng.release(b)                           # waits step 2 out: `a` ended
    assert not eng._active[a] and a not in eng.free_slots()
    assert eng.free_slots() == [b]           # its own events were dropped
    held = eng.drain()
    assert [e.slot for e in held] == [a] and held[0].finished
    assert sorted(eng.free_slots()) == [a, b]


# -- (b) when load stops ----------------------------------------------------


def test_last_tokens_are_delivered_when_the_queue_empties(setup):
    eng = _engine(setup)
    sched = Scheduler(eng)
    reqs = [sched.submit(*_request(i, 6 + i)) for i in range(3)]
    _run(sched, reqs)
    for i, req in enumerate(reqs):
        assert req.result(timeout=1) == _alone(setup, "plain", i, 6 + i)
    assert sched.inflight() == 0 and _idle(eng)
    assert sched.step() == 0 and eng.stats.active_slots == 0
    # engine level: with nothing active, a step ahead reads the one in
    # flight and dispatches nothing
    slot, _ = eng.admit(*_request(0, 2))
    eng.step(ahead=True)                     # the last token is in flight
    steps = eng.stats.decode_steps
    last, = eng.step(ahead=True)
    assert last.finished and last.token == _alone(setup, "plain", 0, 2)[1]
    assert _idle(eng) and eng.stats.decode_steps == steps + 2


# -- park / resume ----------------------------------------------------------


def test_park_and_resume_under_a_step_in_flight_is_byte_identical(setup):
    eng = _engine(setup, num_slots=1)
    sched = Scheduler(eng, preempt=True)
    batch = sched.submit(*_request(0, 30), tenant="b", slo_class="batch")
    for _ in range(6):
        sched.step()
    assert eng._flight is not None and len(batch.tokens) >= 4
    inter = sched.submit(*_request(1, 6), tenant="a",
                         slo_class="interactive")
    _run(sched, [inter, batch])
    assert sched.preemptions == 1 and sched.resumes == 1
    assert inter.done_t < batch.done_t
    assert batch.result(timeout=1) == _alone(setup, "plain", 0, 30)
    assert inter.result(timeout=1) == _alone(setup, "plain", 1, 6)
    assert _idle(eng)


def test_engine_park_waits_the_step_in_flight_out(setup):
    """Direct callers: ``park`` under a step in flight snapshots a row
    that is level with the device (the step's token is held, not lost),
    and the resumed row continues the stream."""
    eng = _engine(setup)
    slot, ev = eng.admit(*_request(0, 20))
    toks = [ev.token]
    toks += [e.token for e in eng.step(ahead=True)]      # nothing yet
    toks += [e.token for e in eng.step(ahead=True)]
    drains = eng.stats.drains                # `admit` waited, once
    parked = eng.park(slot)                  # waits the second step out
    assert eng._flight is None and eng.stats.drains == drains + 1
    toks += [e.token for e in eng.drain()]
    assert parked.generated == len(toks) == 3
    other = eng.resume(parked)
    while other not in eng.free_slots():
        toks += [e.token for e in eng.step()]
    assert toks == _alone(setup, "plain", 0, 20)


# -- the quarantine ---------------------------------------------------------


def test_poisoned_row_is_stopped_by_the_program_itself(setup):
    eng = _engine(setup)
    sched = Scheduler(eng)
    bad = sched.submit(*_request(0, 30))
    good = sched.submit(*_request(1, 12))
    sched.step()
    slot = next(s for s, r in sched._by_slot.items() if r is bad)
    pages = [int(p) for p in eng._bt[slot] if p]
    eng._cache = jax.tree.map(lambda x: x.at[pages[0]].set(jnp.nan),
                              eng._cache)
    for _ in range(4):
        sched.step()
        if bad.status is RequestStatus.FAILED:
            break
    assert isinstance(bad.exception, SlotQuarantinedError)
    assert eng.stats.quarantined == 1
    # no host write: the program cleared the row, nothing went up, and
    # the step that followed the poisoned one was dispatched ahead as ever
    assert not eng._stale and eng.stats.upload_arrays == 0
    assert eng.stats.steps_ahead == eng.stats.decode_steps - 1
    assert not bool(np.asarray(eng._dev["active"])[slot])
    assert not np.asarray(eng._dev["bt"])[slot].any()
    assert slot in eng.free_slots()
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves(eng._cache))      # written over
    # the freed pages are taken again, the poisoned one among them
    again = sched.submit(*_request(2, 12))
    sched.step()
    assert set(pages) & {int(p) for p in eng._bt[slot]}
    _run(sched, [good, again])
    assert good.result(timeout=1) == _alone(setup, "plain", 1, 12)
    assert again.result(timeout=1) == _alone(setup, "plain", 2, 12)


# -- (d) failover -----------------------------------------------------------


def test_failover_discards_the_dead_engines_step_in_flight(setup):
    old = _engine(setup)
    sched = Scheduler(old)
    lost = [sched.submit(*_request(i, 30)) for i in range(2)]
    for _ in range(4):
        sched.step()
    assert old._flight is not None
    had = [list(r.tokens) for r in lost]
    epoch = sched._epoch                     # what a wedged driver holds
    sched.fail_inflight(EngineFailedError("test: engine died"))
    new = _engine(setup)
    sched.replace_engine(new)
    # the stale driver wakes: its engine still answers, nobody listens
    events = old.step(ahead=True)
    assert events and sched._deliver(events, epoch, old) == 0
    assert [list(r.tokens) for r in lost] == had
    assert all(isinstance(r.exception, EngineFailedError) for r in lost)
    fresh = sched.submit(*_request(2, 10))
    _run(sched, [fresh])
    assert fresh.result(timeout=1) == _alone(setup, "plain", 2, 10)
    assert new.stats.prefills == 1 and _idle(new)
    assert new.stats.steps_ahead == new.stats.decode_steps - 1


# -- every kind of engine ---------------------------------------------------


@pytest.mark.parametrize("kind", list(KINDS))
def test_staggered_admissions_equal_one_at_a_time(setup, kind):
    eng = _engine(setup, kind)
    sched = Scheduler(eng)
    lengths = [14, 9, 17, 6, 12]
    reqs = [sched.submit(*_request(i, lengths[i])) for i in range(2)]
    for i in range(2, 5):
        for _ in range(2):                   # arrivals between the rounds
            sched.step()
        reqs.append(sched.submit(*_request(i, lengths[i])))
    _run(sched, reqs)
    for i, req in enumerate(reqs):
        assert req.result(timeout=1) == _alone(setup, kind, i, lengths[i]), i
    st = eng.stats
    assert st.upload_arrays == 0 and st.prefills == 5
    assert st.steps_ahead > 0 and _idle(eng)
    if kind == "spec4":
        assert st.spec_drafted > 0
