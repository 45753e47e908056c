"""The trace reduction and the per-layer metric readers, on a trace built
by hand."""
import pytest

from bench import harness, trace as tr

MS = 1_000_000          # ns


def _trace():
    # window [0, 100 ms); one round.  Device 0 runs a local step 10-30 ms
    # (two ops with a 5 ms hole), a G step 60-70 ms, and an op that
    # straddles the window's end.
    ops = {0: [(10 * MS, 20 * MS, "convolution.1"),
               (25 * MS, 30 * MS, "fusion.2"),
               (60 * MS, 70 * MS, "convolution.1"),
               (95 * MS, 110 * MS, "fusion.3")]}
    modules = {0: [(10 * MS, 30 * MS, "jit_step(11)"),
                   (60 * MS, 70 * MS, "jit_g_step(12)"),
                   (95 * MS, 110 * MS, "jit_gen_batch(13)")]}
    spans = [(0, 100 * MS, "bench.window"),
             (0, 100 * MS, "bench.round"),
             (0, 50 * MS, "bench.engine_round"),
             (0, 8 * MS, "bench.input"),
             (55 * MS, 90 * MS, "bench.generator")]
    return tr.Trace(ops, modules, spans)


def _ctx(**kw):
    base = dict(trace=_trace(), lo=0, hi=100 * MS, devices=[0], rounds=1,
                window_s=0.1, chips=1, flops_per_round=1.97e12,
                peaks={"flops_bf16": 197e12})
    base.update(kw)
    return harness.Context(**base)


def test_union_busy_and_gaps():
    t = _trace()
    assert tr.union([(5, 10), (8, 12), (20, 30)], 0, 25) == [(5, 12),
                                                             (20, 25)]
    assert tr.busy_ns(t.ops[0], 0, 100 * MS) == 30 * MS
    assert tr.gaps(t.ops[0], 0, 100 * MS) == [
        (0, 10 * MS), (20 * MS, 25 * MS), (30 * MS, 60 * MS),
        (70 * MS, 95 * MS)]


def test_time_by_program_strips_ids_and_clips():
    got = tr.time_by_program(_trace().modules[0], 0, 100 * MS)
    assert got == {"jit_step": 20 * MS, "jit_g_step": 10 * MS,
                   "jit_gen_batch": 5 * MS}
    assert tr.program_name("jit_d_step.4") == "jit_d_step"


def test_gaps_go_to_the_innermost_open_span():
    t = _trace()
    got = tr.attribute_gaps(tr.gaps(t.ops[0], 0, 100 * MS), t.spans)
    # 0-10: midpoint 5 ms is inside bench.input (the shortest open span);
    # 20-25 and 30-60 (midpoint 45) inside bench.engine_round;
    # 70-95 (midpoint 82.5) inside bench.generator
    assert got == {"bench.input": 10 * MS, "bench.engine_round": 35 * MS,
                   "bench.generator": 25 * MS}
    outside = tr.attribute_gaps([(200 * MS, 210 * MS)], t.spans)
    assert list(outside.values()) == [10 * MS]


@pytest.mark.parametrize("name,want", [
    ("device.idle_share", 70.0),
    ("client_step.device_ms", 20.0),
    ("generator.device_ms", 15.0),
    ("input.host_ms", 8.0),
    ("mfu", 10.0),
])
def test_metric_readers(name, want):
    assert harness.load_metric(name).read(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["device.idle_share",
                                  "client_step.device_ms",
                                  "generator.device_ms", "input.host_ms"])
def test_reader_with_nothing_to_read_returns_none(name):
    empty = tr.Trace({}, {}, [(0, 100 * MS, "bench.window")])
    assert harness.load_metric(name).read(_ctx(trace=empty)) is None


def test_from_profile_reads_device_ops_modules_and_bench_spans():
    from jax.profiler import ProfileData
    ev = "events {{ metadata_id: {m} offset_ps: {o} duration_ps: {d} }}"
    meta = "event_metadata {{ key: {k} value {{ id: {k} name: \"{n}\" }} }}"
    txt = f"""
    planes {{ id: 1 name: "/device:TPU:0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
               {ev.format(m=1, o=2_000_000, d=5_000_000)} }}
      lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000
               {ev.format(m=2, o=1_000_000, d=7_000_000)} }}
      {meta.format(k=1, n="fusion.1")} {meta.format(k=2, n="jit_step(3)")} }}
    planes {{ id: 2 name: "/device:TPU:0 SparseCore 0"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
               {ev.format(m=1, o=0, d=1_000)} }}
      {meta.format(k=1, n="other")} }}
    planes {{ id: 3 name: "/host:CPU"
      lines {{ id: 1 name: "python" timestamp_ns: 1000
               {ev.format(m=1, o=0, d=10_000_000)}
               {ev.format(m=2, o=0, d=1_000_000)} }}
      {meta.format(k=1, n="bench.window")} {meta.format(k=2, n="pjit")} }}
    """
    t = tr.from_profile(ProfileData.from_text_proto(txt))
    assert t.ops == {0: [(3000, 8000, "fusion.1")]}
    assert t.modules == {0: [(2000, 9000, "jit_step(3)")]}
    assert t.spans == [(1000, 11000, "bench.window")]


def test_ops_are_named_after_their_program():
    t = _trace()
    got = tr.time_by_op(t.ops[0], t.modules[0], 0, 100 * MS)
    assert got == {"jit_step/convolution.1": 10 * MS,
                   "jit_step/fusion.2": 5 * MS,
                   "jit_g_step/convolution.1": 10 * MS,
                   "jit_gen_batch/fusion.3": 5 * MS}
    assert tr.op_name("%fusion.37 = (f32[128]) fusion(f32[1] %a)") == \
        "fusion.37"



def test_start_trace_keeps_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    harness.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        jnp.sin(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert [n for _, _, n in t.spans] == ["bench.window"]
