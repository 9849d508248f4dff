"""Liveness-budget tests for the reliable wire (DESIGN.md §16).

Property-based (hypothesis when installed, the deterministic fallback
otherwise): across arbitrary interleavings of connection tears, receive
timeouts, and eventual delivery, a `ReliableChannel` request

* NEVER livelocks — total peer silence is bounded by
  ``deadline + park budget`` (plus scheduling slack), even when every
  redial succeeds and every window tears again (the pathological
  reconnect loop the park budget must not unbound);
* NEVER dies prematurely — the failure is raised no earlier than the
  deadline, and a response that arrives within the budget is returned,
  not discarded;
* and the responder's idle budget bounds B's total peer silence the
  same way (a dead engine cannot spin `serve_forever` forever).
"""
import time

import pytest

from hypothesis import given, settings, strategies as st
from repro.core.channel import (RESP_BIT, ReliableChannel, Responder,
                                T_EXCHANGE, WireError, WireTimeout,
                                decode_frame, encode_frame)

# scripted fates, one per send attempt
OK, DROP, SEVER = "ok", "drop", "sever"


class ScriptedTransport:
    """A Transport whose per-send fate is a script: `ok` delivers and the
    response is receivable, `drop` loses the frame (recv times out),
    `sever` raises ConnectionError from send. Past the script's end the
    `tail` fate repeats forever. No real I/O, no sleeps — the channel's
    own clocks (try windows, backoff, park) drive all elapsed time."""

    def __init__(self, script, tail=SEVER):
        self.script = list(script)
        self.tail = tail
        self.sends = 0
        self.reconnects = 0
        self._inbox = []

    def _fate(self):
        i = self.sends
        self.sends += 1
        return self.script[i] if i < len(self.script) else self.tail

    def send_frame(self, frame):
        fate = self._fate()
        if fate == SEVER:
            raise ConnectionError("scripted sever")
        if fate == DROP:
            return
        ftype, seq, _payload, _tid = decode_frame(frame, with_trace=True)
        self._inbox.append(encode_frame(ftype | RESP_BIT, seq, b"pong"))

    def recv_frame(self, timeout=None):
        if self._inbox:
            return self._inbox.pop(0)
        raise TimeoutError("scripted silence")

    def reconnect(self):
        self.reconnects += 1

    def close(self):
        pass


def _channel(t, deadline, park):
    # huge retry budget so the TIME budgets are what terminate the loop
    return ReliableChannel(t, deadline_s=deadline, try_timeout_s=0.01,
                           max_retries=10_000, backoff_s=0.001,
                           backoff_max_s=0.01, reconnect_wait_s=park)


@given(st.lists(st.sampled_from([DROP, SEVER]), min_size=0, max_size=12),
       st.sampled_from([DROP, SEVER]),
       st.floats(min_value=0.0, max_value=0.25))
@settings(max_examples=25, deadline=None)
def test_total_silence_is_bounded_no_livelock_no_early_death(
        prefix, tail, park):
    """All-failure schedules: the request must fail, no earlier than the
    deadline and no later than deadline + park + slack — for EVERY
    interleaving of drops and severs, parked or not."""
    deadline = 0.25
    t = ScriptedTransport(prefix, tail=tail)
    chan = _channel(t, deadline, park)
    t0 = time.monotonic()
    with pytest.raises((WireTimeout, WireError)):
        chan.request(T_EXCHANGE, b"x")
    elapsed = time.monotonic() - t0
    assert elapsed >= deadline - 0.02, \
        f"died prematurely after {elapsed:.3f}s (deadline {deadline}s)"
    assert elapsed <= deadline + park + 1.0, \
        f"livelock: {elapsed:.3f}s > deadline+park ({deadline}+{park}s)"


@given(st.lists(st.sampled_from([DROP, SEVER]), min_size=0, max_size=8))
@settings(max_examples=25, deadline=None)
def test_delivery_within_budget_always_succeeds(prefix):
    """Any failure prefix short enough to leave budget must NOT kill the
    request: the eventual delivery is returned."""
    t = ScriptedTransport(list(prefix) + [OK], tail=OK)
    chan = _channel(t, deadline=10.0, park=10.0)
    assert chan.request(T_EXCHANGE, b"x") == b"pong"


def test_park_budget_not_consumed_by_clean_requests():
    """Parking is per-request and only on tears: a clean request after a
    parked one starts with the full budget again."""
    t = ScriptedTransport([SEVER, SEVER, OK, OK], tail=OK)
    chan = _channel(t, deadline=5.0, park=5.0)
    assert chan.request(T_EXCHANGE, b"a") == b"pong"
    parked_first = chan.parked_s
    assert parked_first > 0.0
    assert chan.request(T_EXCHANGE, b"b") == b"pong"
    assert chan.parked_s == parked_first     # no parking without a tear


def test_zero_park_budget_keeps_legacy_fail_fast():
    """reconnect_wait_s=0 (the default): tears charge the retry budget
    immediately — the unsupervised deployments' fail-fast behaviour."""
    t = ScriptedTransport([], tail=SEVER)
    chan = ReliableChannel(t, deadline_s=30.0, try_timeout_s=0.01,
                           max_retries=3, backoff_s=0.001,
                           backoff_max_s=0.002)
    t0 = time.monotonic()
    with pytest.raises(WireError, match="retries exhausted"):
        chan.request(T_EXCHANGE, b"x")
    assert time.monotonic() - t0 < 1.0
    assert chan.parked_s == 0.0


class DeadEngineTransport:
    """Responder-side fake: the engine is gone — every recv tears."""

    def __init__(self):
        self.reconnects = 0

    def recv_frame(self, timeout=None):
        raise ConnectionError("peer gone")

    def send_frame(self, frame):
        raise ConnectionError("peer gone")

    def reconnect(self):
        self.reconnects += 1

    def close(self):
        pass


def test_responder_idle_budget_bounds_dead_engine_spin():
    """B's serve loop must not livelock redialing a dead engine: total
    silence is capped by idle_timeout_s even though every recv raises
    ConnectionError (never TimeoutError)."""
    t = DeadEngineTransport()
    r = Responder(t, handler=lambda ftype, payload: b"", idle_timeout_s=0.3)
    t0 = time.monotonic()
    with pytest.raises(WireTimeout, match="silent"):
        r.serve_forever()
    elapsed = time.monotonic() - t0
    assert 0.25 <= elapsed <= 5.0
    assert t.reconnects > 0
