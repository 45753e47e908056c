"""``correct`` on the mnist-paper cell, on the CPU at small sizes."""
import pytest

from bench.tests import faults

CELL = "mnist-paper"


def test_sound_passes_and_control_fails():
    faults.sound_passes_and_control_fails(CELL)


def test_unchanged_state_fails(monkeypatch):
    faults.unchanged_state_fails(CELL, monkeypatch)


def test_half_batch_fails(monkeypatch):
    faults.half_batch_fails(CELL, monkeypatch)


@pytest.mark.parametrize("fault", [
    faults.foreign_rows_fail, faults.repeated_latents_fail,
    faults.narrow_latents_fail, faults.another_draw_order_passes],
    ids=lambda f: f.__name__)
def test_draws(fault, monkeypatch):
    fault(CELL, monkeypatch)
