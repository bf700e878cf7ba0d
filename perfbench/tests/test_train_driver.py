"""The ``train`` driver's pipelined loop against a fake trainer: the bound on
steps in flight, completions in order, and what a failing step turns into."""

import itertools
import threading
import time

import pytest

from perfbench import cells

train = cells.load_plugin("drivers", "train")


class FakeLoss:
    """Stands in for the device scalar: ``float()`` blocks until the 'device'
    has finished the step, as a readback does."""

    def __init__(self, trainer, value, seconds):
        self.trainer, self.value, self.seconds = trainer, value, seconds

    def __float__(self):
        time.sleep(self.seconds)
        with self.trainer.lock:
            self.trainer.in_flight -= 1
        if isinstance(self.value, Exception):
            raise self.value
        return float(self.value)


class FakeTrainer:
    def __init__(self, losses, seconds=0.002):
        self.losses = iter(losses)
        self.seconds = seconds
        self.lock = threading.Lock()
        self.in_flight = 0
        self.most_in_flight = 0
        self.batches_seen = []

    def train_step(self, state, batch):
        value = next(self.losses)
        if value == "raise":
            raise RuntimeError("step failed at dispatch")
        with self.lock:
            self.in_flight += 1
            self.most_in_flight = max(self.most_in_flight, self.in_flight)
        self.batches_seen.append(batch)
        return state + 1, FakeLoss(self, value, self.seconds)


@pytest.mark.parametrize("max_in_flight", [1, 2, 4])
def test_never_more_steps_in_flight_than_allowed(max_in_flight):
    trainer = FakeTrainer(itertools.count(1.0))
    out = train.drive(trainer, 0, itertools.count(), train.Spans(),
                      max_in_flight, lambda n: n >= 60)
    assert out.error is None and out.dispatched == 60 and out.state == 60
    assert trainer.most_in_flight == max_in_flight
    # every dispatched step completed before drive returned, in order
    assert [v for _, v in out.completed] == [float(i) for i in range(1, 61)]
    stamps = [t for t, _ in out.completed]
    assert stamps == sorted(stamps) and out.begin <= stamps[0] <= out.end
    assert trainer.batches_seen == list(range(60))   # a fresh batch each step


def test_spans_record_one_duration_per_call():
    spans = train.Spans()
    train.drive(FakeTrainer(itertools.count(1.0)), 0, itertools.count(), spans,
                2, lambda n: n >= 10)
    assert {k: len(v) for k, v in spans.durations.items()} == {
        "bench/in_flight_wait": 10, "bench/next_batch": 10,
        "bench/train_step": 10}
    assert all(d >= 0 for v in spans.durations.values() for d in v)


def test_a_step_that_raises_ends_the_stretch_and_is_reported():
    trainer = FakeTrainer([1.0, 2.0, "raise", 4.0])
    out = train.drive(trainer, 0, itertools.count(), train.Spans(), 2,
                      lambda n: n >= 4)
    assert isinstance(out.error, RuntimeError)
    assert out.dispatched == 2 and [v for _, v in out.completed] == [1.0, 2.0]


def test_a_loss_that_cannot_be_read_back_completes_as_not_finite():
    trainer = FakeTrainer([1.0, RuntimeError("device lost"), 3.0])
    out = train.drive(trainer, 0, itertools.count(), train.Spans(), 2,
                      lambda n: n >= 3)
    values = [v for _, v in out.completed]
    assert out.error is None and len(values) == 3
    assert values[0] == 1.0 and values[1] != values[1] and values[2] == 3.0
