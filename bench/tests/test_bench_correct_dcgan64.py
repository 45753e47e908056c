"""``correct`` on the dcgan64-paper cell, on the CPU at small sizes: the
published layouts of G and D at 16x16x3 (two transposed convs, two conv
blocks), so that the plain reference's per-tap conv gradient stays quick."""
import pytest

from bench.tests import faults, small

CELL = "dcgan64-paper"
SIZE = {"image_size": 16, "conv_blocks": 2}
_tweak = small.tweak


def tweak(conf, cell):
    conf, cell = _tweak(conf, cell)
    return {**conf, "model": {**conf["model"], **SIZE},
            "override": {**conf["override"],
                         **{f"model.dcgan.{k}": v for k, v in SIZE.items()}}
            }, cell


@pytest.fixture(autouse=True)
def smaller(monkeypatch):
    monkeypatch.setattr(small, "tweak", tweak)


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
