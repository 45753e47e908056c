"""The program's own spans on the profiler's clock (``fsl.*``, named in
``repro.obs.trace``) in a profiler trace of one ``mnist-paper`` round,
on the CPU at the benchmark tests' small sizes."""
import glob
import importlib
import os

import jax
import pytest
from jax.profiler import ProfileData

from bench import harness
from bench.tests import small
from repro.obs import trace as ot

CELL = "mnist-paper"
LAYERS = (ot.SPAN_INPUT, ot.SPAN_CLIENT_STEP, ot.SPAN_REDUCE,
          ot.SPAN_GENERATOR)
NAMES = (ot.SPAN_ROUND, ot.SPAN_SYNC) + LAYERS


def program_spans(log_dir):
    """``(start_ns, end_ns, name)`` of every host event named by a
    ``SPAN_*`` constant, in the trace written under ``log_dir``."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(int(e.start_ns), int(e.end_ns), e.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events
            if e.name in NAMES]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    _, _, conf, cell = harness.load_cell(CELL)
    conf, cell = small.tweak(conf, cell)
    system = importlib.import_module(f"bench.systems.{conf['family']}") \
        .System(conf, cell, harness.seeds_from(2 ** 32 + 5))
    system.round()                      # compiles outside the trace
    log_dir = str(tmp_path_factory.mktemp("trace"))
    harness.start_trace(log_dir)
    try:
        system.round()
    finally:
        jax.profiler.stop_trace()
    return program_spans(log_dir), cell


def test_layer_spans_lie_inside_the_round(traced):
    spans, _ = traced
    rounds = [(s, e) for s, e, n in spans if n == ot.SPAN_ROUND]
    assert len(rounds) == 1
    inner = [(s, e, n) for s, e, n in spans if n in LAYERS]
    assert {n for _, _, n in inner} == set(LAYERS)
    assert all(any(rs <= s and e <= re for rs, re in rounds)
               for s, e, _ in inner)


def test_one_input_and_one_client_step_span_per_client(traced):
    spans, cell = traced
    names = [n for _, _, n in spans]
    assert names.count(ot.SPAN_INPUT) == cell["clients"]
    assert names.count(ot.SPAN_CLIENT_STEP) == cell["clients"]
    assert names.count(ot.SPAN_REDUCE) == names.count(ot.SPAN_GENERATOR) == 1


def test_one_sync_span_per_loss_read(traced):
    spans, cell = traced
    batches = cell["batches_per_client"]
    syncs = [(s, e) for s, e, n in spans if n == ot.SPAN_SYNC]
    assert len(syncs) == cell["clients"] * batches + batches
    # the local steps' reads nest in their client's span, the G steps'
    # in the generator's
    outer = [(s, e) for s, e, n in spans
             if n in (ot.SPAN_CLIENT_STEP, ot.SPAN_GENERATOR)]
    assert all(any(os_ <= s and e <= oe for os_, oe in outer)
               for s, e in syncs)


def test_reduce_span_bounds_host_time(traced):
    spans, _ = traced
    (s, e), = [(s, e) for s, e, n in spans if n == ot.SPAN_REDUCE]
    gens = [gs for gs, _, n in spans if n == ot.SPAN_GENERATOR]
    steps = [ce for _, ce, n in spans if n == ot.SPAN_CLIENT_STEP]
    # the reduce takes host time, after every client's steps and before G
    assert e > s
    assert max(steps) <= s and e <= min(gens)
