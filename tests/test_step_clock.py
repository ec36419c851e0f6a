"""The Trainer's step clock: one measurement of a step, taken where the
step ends.

JAX returns from a jitted call before the device is done, so a clock
round the dispatch reads microseconds for a step of any length. These
tests drive the Trainer's loop over a stand-in device that really takes
time *after* dispatch (a serial queue: a step starts when the one
before it ends, and its loss is ready ``STEP_S`` later) and hold every
reader of the step's time to the completion: the ``step.end`` event
(goodput ledger, the master's median step and hang check), the
``train.step.last_s`` gauge, the shm timer ring's ``Tag.STEP`` record
and the sampler's ``on_step_end``.
"""

import logging
import statistics
import time

import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import telemetry
from dlrover_tpu.trainer import trainer as trainer_mod
from dlrover_tpu.trainer.timer import Tag
from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

STEP_S = 0.04
TOLERANCE = 0.2   # of the real step


@pytest.fixture(autouse=True)
def _isolate(isolated_ckpt_env):
    yield


@pytest.fixture
def fresh_telemetry():
    prev = telemetry.active_registry()
    reg = telemetry.enable(source="worker-0-1")
    reg.role = "worker"
    yield reg
    telemetry._REGISTRY = prev


class LateLoss:
    """A loss whose readiness comes late, as a device array's does. The
    first look at it leaves in ``late`` how long after its readiness
    the host woke to see it."""

    def __init__(self, ready_at, value, late):
        self.ready_at, self.value, self.late = ready_at, value, late
        self.looked = False

    def block_until_ready(self):
        wait = self.ready_at - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        if not self.looked:
            self.looked = True
            self.late.append(max(time.monotonic() - self.ready_at, 0.0))
        return self

    def __float__(self):
        self.block_until_ready()
        return self.value


class SlowDevice:
    """Stands in for ``_accel.train_step``: returns at once, the step
    completes ``STEP_S`` after the later of its dispatch and the
    completion of the step before it. ``done_at`` is when each step
    completed, ``late`` how late the host woke to each completion."""

    def __init__(self, step_s=STEP_S):
        self.step_s = step_s
        self.free_at = 0.0
        self.dispatch_s = []
        self.done_at = []
        self.late = []

    def __call__(self, state, batch, rng):
        t0 = time.monotonic()
        self.free_at = max(self.free_at, t0) + self.step_s
        self.done_at.append(self.free_at)
        loss = LateLoss(self.free_at, 1.0 / (1 + len(self.dispatch_s)),
                        self.late)
        self.dispatch_s.append(time.monotonic() - t0)
        return state, {"loss": loss}

    def measured(self, durs):
        """Whether ``durs`` are the steady steps as this device saw
        them, one by one and in their sum: each between the step's own
        length and the time from the completion before it to its own
        (longer where the device waited for a late dispatch). The
        tolerance is what the device measured too: a reader sees a
        completion when the host wakes to it, so a step may read longer
        by that wake's lateness and shorter by the one before it (a
        loaded test host wakes tens of ms late from a sleep; a constant
        share of the step cannot know). A dispatch time among them, or
        in place of them, fails the lower bounds."""
        durs, true = list(durs), np.diff(self.done_at)
        slack = 2 * max(self.late) + 2e-3
        steady = len(true) * self.step_s
        return (
            len(durs) == len(true) and slack < steady / 2
            and steady - slack <= sum(durs) <= true.sum() + slack
            and all(self.step_s - slack <= d <= t + slack
                    for d, t in zip(durs, true))
        )


class RingRecorder:
    """In place of the shm timer ring."""

    def __init__(self):
        self.records = []

    def record(self, tag, start_ns, dur_ns):
        self.records.append((tag, start_ns, dur_ns))


class SamplerRecorder:
    def __init__(self):
        self.ends = []

    def on_step_start(self, step):
        pass

    def on_step_end(self, step, dur_s=0.0, block_on=None):
        self.ends.append((step, dur_s))

    def close(self):
        pass


def make_trainer(tmp_path, steps, **kw):
    def init_fn(rng):
        return {"w": jnp.zeros((4, 1))}

    def loss_fn(params, batch, rng):
        return jnp.mean((batch @ params["w"]) ** 2)

    args = TrainingArgs(
        output_dir=str(tmp_path / "out"), max_steps=steps,
        **{"flash_checkpoint": False, "log_steps": 0, **kw},
    )
    data = [np.ones((4, 4), np.float32)] * steps
    trainer = Trainer(
        loss_fn, init_fn, {"w": (None, None)}, args, train_data=data
    )
    device = SlowDevice()
    trainer._accel.train_step = device
    trainer._timer = RingRecorder()
    trainer._prof.close()
    trainer._prof = SamplerRecorder()
    return trainer, device


def real_steps(durs, step_s=STEP_S):
    """The real step to 20% in the median, and no dispatch time among
    them (a loaded test host wakes late from one sleep and early for
    the next, so single steps may stray further)."""
    durs = list(durs)
    return (
        statistics.median(durs) == pytest.approx(step_s, rel=TOLERANCE)
        and min(durs) > step_s / 2
    )


def step_events(kind="step.end"):
    return [e for e in telemetry.snapshot()["events"] if e["kind"] == kind]


def test_every_reader_gets_the_completion_not_the_dispatch(
    tmp_path, fresh_telemetry,
):
    steps = 14
    trainer, device = make_trainer(tmp_path, steps, log_steps=5)
    t0 = time.time()
    trainer.train()
    wall = time.time() - t0
    trainer.close()
    # the stand-in's dispatch really is nothing beside its step
    assert statistics.median(device.dispatch_s) < STEP_S / 10

    # one step.end per steady step, in order; the first step compiles
    events = step_events()
    assert [e["step"] for e in events] == list(range(2, steps + 1))
    assert [e["step"] for e in step_events("compile")] == [1]
    # each is the step the device took, and they tile its run: nothing
    # is counted twice or lost
    assert device.measured(e["dur"] for e in events)
    booked = sum(e["dur"] for e in events)
    assert booked + step_events("compile")[0]["dur"] <= wall

    snap = telemetry.snapshot()
    series = {s["name"]: s["points"] for s in snap["series"]}
    assert len(series["train.step.last_s"]) == steps - 1
    assert device.measured(p[3] for p in series["train.step.last_s"])
    # the same number, not a second measurement
    assert [p[3] for p in series["train.step.last_s"]] == \
        [e["dur"] for e in events]

    ring = [r for r in trainer._timer.records
            if r[0] in (Tag.STEP, Tag.COMPILE)]
    assert [r[0] for r in ring] == [Tag.COMPILE] + [Tag.STEP] * (steps - 1)
    assert device.measured(dur / 1e9 for _tag, _start, dur in ring[1:])
    # a record's start is where the step before it ended
    for (_t, start, dur), (_t2, start2, _d2) in zip(ring, ring[1:]):
        assert start2 >= start + dur

    # the sampler's governor: the step's own number, by its own
    # (pre-increment) numbering
    assert [s for s, _dur in trainer._prof.ends] == list(range(steps))
    assert trainer._prof.ends[0][1] == 0.0   # the compiling step: none
    assert device.measured(dur for _s, dur in trainer._prof.ends[1:])

    # nothing the Trainer publishes carries a dispatch time
    names = {g["name"] for g in snap["gauges"]} \
        | {h["name"] for h in snap["histograms"]}
    assert not names & {
        "train.step.seconds", "train.steps_per_s", "train.tokens_per_s",
    }


def test_the_loop_keeps_step_lag_steps_in_flight(tmp_path, fresh_telemetry):
    """After dispatching step k the loop has seen step k - STEP_LAG
    complete, and no later one: the device always has work queued."""
    steps = 8
    trainer, device = make_trainer(tmp_path, steps)
    seen = []
    plain = device.__call__

    def dispatch(state, batch, rng):
        seen.append(trainer._completed_step)
        return plain(state, batch, rng)

    trainer._accel.train_step = dispatch
    trainer.train()
    trainer.close()
    # dispatching step k (1-based), steps up to k - 1 - STEP_LAG are done
    lag = trainer_mod.STEP_LAG
    assert seen == [max(k - 1 - lag, 0) for k in range(1, steps + 1)]
    assert trainer._completed_step == steps and not trainer._pending


class _ReadbackTap(logging.Handler):
    def __init__(self):
        super().__init__()
        self.closed_at_readback = []

    def emit(self, record):
        if str(record.msg).startswith("step %d epoch %d loss"):
            self.closed_at_readback.append(
                (record.args[0], [e["step"] for e in step_events()][-1])
            )


def test_pending_steps_are_closed_at_readback_and_end(
    tmp_path, fresh_telemetry,
):
    steps = 11
    trainer, _device = make_trainer(tmp_path, steps, log_steps=4)
    tap = _ReadbackTap()
    logger = logging.getLogger("dlrover_tpu.trainer.trainer")
    logger.addHandler(tap)
    try:
        trainer.train()
    finally:
        logger.removeHandler(tap)
        trainer.close()
    # at each read-back the step read back has its step.end already
    assert tap.closed_at_readback == [(4, 4), (8, 8)]
    # and at the end of train() so has the last one
    assert [e["step"] for e in step_events()][-1] == steps


def test_pending_steps_are_closed_before_a_save(tmp_path, fresh_telemetry):
    steps = 9
    trainer, _device = make_trainer(
        tmp_path, steps, flash_checkpoint=True, save_steps=4,
        save_storage_every=1000,
    )
    closed_at_save = []
    plain = trainer._engine.save_to_memory

    def save(step, tree):
        closed_at_save.append((step, [e["step"] for e in step_events()][-1]))
        return plain(step, tree)

    trainer._engine.save_to_memory = save
    try:
        trainer.train()
    finally:
        trainer.close()
    assert closed_at_save[:2] == [(4, 4), (8, 8)]
    # the save's seconds are booked to no step
    saves = step_events("ckpt.save")
    assert len(saves) >= 2
    assert real_steps(e["dur"] for e in step_events())


def test_goodput_ledger_reads_a_healthy_job_as_productive(
    tmp_path, fresh_telemetry,
):
    trainer, _device = make_trainer(tmp_path, 30, log_steps=10)
    trainer.train()
    trainer.close()
    ledger = telemetry.goodput_ledger([telemetry.snapshot()])
    assert ledger["goodput"] >= 0.9, ledger
    # with the dispatch booked as the step it read a few percent
    assert ledger["categories"]["idle"] <= 0.1 * ledger["total_s"]


def test_hang_check_holds_through_a_save_of_twenty_steps(
    tmp_path, fresh_telemetry,
):
    """The master takes a hang for no ``step.end`` in factor x the
    median step. With the median a dispatch time the factor bought
    nothing and any save looked like a hang; on the real step a save of
    20 step-times stays under a factor of 30, and one of 40 does not."""
    from dlrover_tpu.master.diagnosis import DiagnosisManager

    trainer, _device = make_trainer(tmp_path, 16, log_steps=8)
    trainer.train()
    trainer.close()
    snap = telemetry.snapshot()
    last = max(e["t"] for e in snap["events"] if e["kind"] == "step.end")
    jt = telemetry.JobTelemetry()
    assert jt.update(snap)
    mgr = DiagnosisManager(jt, hang_factor=30.0, hang_floor_s=STEP_S)
    assert mgr.detect_hangs(now=last + 20 * STEP_S) == {}
    hang = mgr.detect_hangs(now=last + 40 * STEP_S)
    assert list(hang) == [0]
    assert hang[0]["median_step_s"] == pytest.approx(STEP_S, rel=TOLERANCE)


def test_a_host_bound_loop_reads_its_own_iteration(tmp_path, fresh_telemetry):
    """A completion is seen when the host looks. Where the host is the
    slower side (here a batch takes twice a step to arrive) a step
    reads the host's iteration, durations still tile the wall clock,
    and the wait has its own record to say why (``Tag.DATA_WAIT``)."""
    steps, wait_s = 8, 2 * STEP_S

    class SlowData:
        def __iter__(self):
            for _ in range(steps):
                time.sleep(wait_s)
                yield np.ones((4, 4), np.float32)

    trainer, _device = make_trainer(tmp_path, steps)
    trainer.train_data = SlowData()
    trainer.train()
    trainer.close()
    events = step_events()
    # all but the last, which the end of train() waits for at once
    assert real_steps((e["dur"] for e in events[:-1]), wait_s)
    span = events[-1]["t"] - (events[0]["t"] - events[0]["dur"])
    assert sum(e["dur"] for e in events) == pytest.approx(span, rel=0.15)
    waits = [r for r in trainer._timer.records if r[0] == Tag.DATA_WAIT]
    assert len(waits) == steps
    assert all(dur / 1e9 >= wait_s for _t, _s, dur in waits)


def test_the_ring_gets_two_events_a_step(tmp_path, fresh_telemetry):
    """The ring is also the flight recorder's payload: the loop's new
    spans (data_wait, step_wait, readback, publish) are trace-only."""
    import collections

    steps = 10
    trainer, _device = make_trainer(tmp_path, steps, log_steps=5)
    trainer.train()
    trainer.close()
    kinds = collections.Counter(
        (e["kind"], e.get("name")) for e in telemetry.snapshot()["events"]
    )
    assert kinds == {
        ("span", "train.dispatch"): steps,
        ("step.end", None): steps - 1,
        ("compile", None): 1,
    }


def test_the_clock_runs_with_telemetry_off(tmp_path):
    prev = telemetry.active_registry()
    telemetry.disable()
    try:
        trainer, _device = make_trainer(tmp_path, 6, log_steps=3)
        trainer.train()
        trainer.close()
    finally:
        telemetry._REGISTRY = prev
    assert trainer._completed_step == 6
    assert len([r for r in trainer._timer.records if r[0] == Tag.STEP]) == 5


def test_the_loop_is_covered_by_its_spans(
    tmp_path, fresh_telemetry, profiled_spans,
):
    """In a profiler session the loop's thread shows what it did
    between two device steps, by name and without holes: data_wait,
    dispatch, step_wait, publish and, at a log boundary, readback."""
    steps = 8
    trainer, _device = make_trainer(tmp_path, steps, log_steps=4)
    try:
        recorded = profiled_spans(trainer.train)
    finally:
        trainer.close()
    spans = sorted(
        (s for s in recorded if s["name"].startswith("train.")),
        key=lambda s: s["start_ns"],
    )
    names = [s["name"] for s in spans]
    assert names.count("train.dispatch") == steps
    assert names.count("train.data_wait") == steps
    assert names.count("train.step_wait") == steps
    assert names.count("train.readback") == 2 * 2    # float(loss) | the rest
    assert names.count("train.publish") >= steps
    assert len({s["thread"] for s in spans}) == 1
    # the step's number rides on the dispatch
    assert [s["stats"]["step"] for s in spans
            if s["name"] == "train.dispatch"] == list(range(1, steps + 1))
    # flat, and without holes from the second dispatch on (the first
    # compiles the random key): what lies between two spans is the
    # loop's own few lines (and, on a loaded test host, a preemption)
    steady = spans[names.index("train.dispatch", 1):]
    for a, b in zip(steady, steady[1:]):
        assert b["start_ns"] >= a["start_ns"] + a["dur_ns"], \
            (a["name"], b["name"])
    covered = sum(s["dur_ns"] for s in steady)
    whole = steady[-1]["start_ns"] + steady[-1]["dur_ns"] \
        - steady[0]["start_ns"]
    assert covered >= 0.9 * whole
