"""Retry engine: the classification table, backoff determinism, deadline
watchdogs, and the retry loop's give-up semantics."""

import time

import pytest

from keystone_tpu.reliability import (
    CorruptRecordError,
    Deadline,
    DeadlineExceeded,
    ErrorClass,
    RetryPolicy,
    classify_error,
    get_recovery_log,
    run_with_deadline,
    wait_until,
)


# ------------------------------------------------------------ classification


@pytest.mark.parametrize(
    "exc,expected",
    [
        (RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating 1.2G"), ErrorClass.OOM),
        (ValueError("XLA allocation failure: Out of memory"), ErrorClass.OOM),
        (MemoryError(), ErrorClass.OOM),
        (RuntimeError("UNAVAILABLE: socket closed"), ErrorClass.TRANSIENT),
        (RuntimeError("coordinator heartbeat missed"), ErrorClass.TRANSIENT),
        (RuntimeError("worker preempted by scheduler"), ErrorClass.TRANSIENT),
        (ConnectionResetError("peer reset"), ErrorClass.TRANSIENT),
        (TimeoutError("no response"), ErrorClass.TRANSIENT),
        (DeadlineExceeded("node: deadline"), ErrorClass.DEADLINE),
        (RuntimeError("DEADLINE_EXCEEDED: rpc"), ErrorClass.DEADLINE),
        (CorruptRecordError("bad jpeg"), ErrorClass.CORRUPT_DATA),
        (RuntimeError("DATA_LOSS: truncated record"), ErrorClass.CORRUPT_DATA),
        (ValueError("block size 12 not divisible"), ErrorClass.PERMANENT),
        (TypeError("estimator dependencies must be datasets"), ErrorClass.PERMANENT),
        (FileNotFoundError("no archive(s) at /x"), ErrorClass.PERMANENT),
        (OSError("stale NFS file handle"), ErrorClass.TRANSIENT),
        (KeyError("label"), ErrorClass.PERMANENT),
    ],
)
def test_classification_table(exc, expected):
    assert classify_error(exc) is expected


def test_message_pattern_wins_over_type():
    # An OOM surfaced through a ValueError path must still walk the
    # degradation ladder, not be treated as a user error.
    assert classify_error(ValueError("RESOURCE_EXHAUSTED while compiling")) is ErrorClass.OOM


# ------------------------------------------------------------------- backoff


def test_backoff_schedule_is_deterministic_per_seed():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.1, multiplier=2.0, seed=42)
    assert p.backoff_schedule() == p.backoff_schedule()
    assert p.backoff_schedule() != RetryPolicy(
        max_attempts=5, base_delay_s=0.1, multiplier=2.0, seed=43
    ).backoff_schedule()
    # exponential envelope: each delay within jitter of base * mult^i
    for i, d in enumerate(p.backoff_schedule()):
        nominal = 0.1 * 2.0**i
        assert nominal * (1 - p.jitter) <= d <= nominal * (1 + p.jitter)


def test_backoff_respects_max_delay():
    p = RetryPolicy(max_attempts=10, base_delay_s=1.0, multiplier=10.0,
                    max_delay_s=3.0, jitter=0.0, seed=0)
    assert p.backoff_schedule() == [1.0, 3.0, 3.0, 3.0, 3.0, 3.0, 3.0, 3.0, 3.0]


def test_call_sleeps_the_published_schedule(no_sleep_policy):
    policy, slept = no_sleep_policy
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("UNAVAILABLE: coordinator hiccup")
        return "ok"

    assert policy.call(flaky, label="flaky") == "ok"
    assert slept == policy.backoff_schedule()[: len(slept)]
    assert len(calls) == 3
    retries = get_recovery_log().events("retry")
    assert len(retries) >= 2
    assert retries[-1].detail["error_class"] == "transient"


def test_call_never_retries_permanent(no_sleep_policy):
    policy, slept = no_sleep_policy
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("bad shape")

    with pytest.raises(ValueError):
        policy.call(broken)
    assert len(calls) == 1 and slept == []


def test_call_never_retries_oom_by_default(no_sleep_policy):
    # OOM is the ladder's job: retrying the same shape re-OOMs.
    policy, slept = no_sleep_policy
    with pytest.raises(RuntimeError):
        policy.call(lambda: (_ for _ in ()).throw(
            RuntimeError("RESOURCE_EXHAUSTED")))
    assert slept == []


def test_call_gives_up_after_max_attempts(no_sleep_policy):
    policy, slept = no_sleep_policy
    calls = []

    def always_down():
        calls.append(1)
        raise ConnectionError("UNAVAILABLE")

    with pytest.raises(ConnectionError):
        policy.call(always_down)
    assert len(calls) == policy.max_attempts
    assert len(slept) == policy.max_attempts - 1


# ----------------------------------------------------------------- deadlines


def test_run_with_deadline_passes_result_and_errors():
    assert run_with_deadline(lambda: 7, 5.0) == 7
    with pytest.raises(ValueError, match="inner"):
        run_with_deadline(lambda: (_ for _ in ()).throw(ValueError("inner")), 5.0)


def test_run_with_deadline_times_out():
    with pytest.raises(DeadlineExceeded, match="hung-node"):
        run_with_deadline(lambda: time.sleep(5.0), 0.1, label="hung-node")


def test_policy_deadline_recovers_hang():
    attempts = []

    def hangs_once():
        attempts.append(1)
        if len(attempts) == 1:
            time.sleep(5.0)
        return "late but fine"

    policy = RetryPolicy(max_attempts=2, deadline_s=0.2, sleep=lambda s: None)
    assert policy.call(hangs_once, label="hang") == "late but fine"
    assert len(attempts) == 2


def test_wait_until_polls_then_deadline():
    state = {"n": 0}

    def pred():
        state["n"] += 1
        return state["n"] >= 3

    assert wait_until(pred, Deadline.after(5.0), interval=0.0, sleep=lambda s: None)
    with pytest.raises(DeadlineExceeded, match="coordinator"):
        wait_until(lambda: False, Deadline.after(0.05), interval=0.01,
                   label="coordinator")


# --------------------------------------------- retry bounded by a deadline


def test_call_stops_retrying_past_the_deadline():
    """The retry clock and the request deadline are ONE clock: when the
    next backoff would sleep past the caller's remaining budget, the
    last error surfaces instead of a retry the deadline has already
    disowned (the serving _apply_group contract)."""
    fake_now = [100.0]
    slept = []
    policy = RetryPolicy(
        max_attempts=5, base_delay_s=1.0, multiplier=1.0, jitter=0.0, seed=0,
        sleep=lambda s: (slept.append(s), fake_now.__setitem__(0, fake_now[0] + s)),
    )
    deadline = Deadline(2.5, clock=lambda: fake_now[0])

    def always_transient():
        raise ConnectionError("UNAVAILABLE: flaky")

    with pytest.raises(ConnectionError):
        policy.call(always_transient, label="bounded", deadline=deadline)
    # budget 2.5s, 1s backoffs: attempt, sleep, attempt, sleep, attempt,
    # then the third backoff (0.5s left < 1s delay) abandons.
    assert slept == [1.0, 1.0]
    abandoned = get_recovery_log().events("retry_abandoned")
    assert abandoned and abandoned[-1].detail["attempt"] == 3


def test_call_with_roomy_deadline_retries_normally():
    policy = RetryPolicy(
        max_attempts=3, base_delay_s=0.001, jitter=0.0, seed=0
    )
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise ConnectionError("UNAVAILABLE: flaky")
        return "ok"

    assert policy.call(flaky, deadline=Deadline(30.0)) == "ok"
    assert attempts["n"] == 3
