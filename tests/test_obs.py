"""Flight recorder (obs/): tracing, metrics, record+replay, profiling.

Pinned invariants (ISSUE 6 acceptance):
  * spans nest and stay monotone on the engine's virtual clock, including
    across engine rebuilds (the tracer re-anchors with a virtual offset);
  * the exported trace is valid Chrome-trace JSON with a span for every
    boundary crossing of a split round;
  * a recorded run's feedback JSONL replayed offline through the PR-5
    controller fold reproduces the live knob sequence BIT-EXACTLY;
  * observability off is the default and a run with obs on is bit-exact
    with the same run with obs off (measurement never steers);
  * kernel profiling is gated off by default (a probe, not training).
"""
import json
import math
import os

import jax
import pytest

from repro.configs.registry import get_config
from repro.control import ControlKnobs, knobs_from_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist
from repro.obs import (FlightRecorder, JsonlSink, MetricsRegistry, Tracer,
                       feedback_from_dict, feedback_to_dict, knobs_from_dict,
                       knobs_to_dict, load_jsonl, load_run, replay_decisions,
                       replay_run, validate_chrome_trace)


def _cfg(**over):
    base = {"shape.global_batch": 8, "fsl.num_clients": 2,
            "model.dcgan.base_filters": 8}
    base.update(over)
    return get_config("dcgan-mnist").override(base)


@pytest.fixture(scope="module")
def parts():
    imgs, labels = synthetic_mnist(120, seed=0)
    return partition_dirichlet(imgs, labels, 2, alpha=0.5, seed=0)


@pytest.fixture(scope="module")
def recorded_run(tmp_path_factory, parts):
    """One adaptive split run recorded end-to-end; shared by the replay,
    trace-schema, and span tests below."""
    out = str(tmp_path_factory.mktemp("obs"))
    cfg = _cfg(**{
        "split.enabled": True,
        "control.mode": "adaptive",
        "control.controllers": ["codec", "deadline"],
        "obs.enabled": True, "obs.out_dir": out, "obs.run_id": "pin"})
    tr = FSLGANTrainer(cfg, parts, seed=0)
    for _ in range(3):
        tr.train_epoch(batches_per_client=2)
    tr.recorder.flush()
    return tr, os.path.join(out, "pin")


# ---------------------------------------------------------------------------
# tracer unit behavior
# ---------------------------------------------------------------------------

def test_tracer_virtual_offset_keeps_clock_monotone():
    """The engine's virtual clock resets to 0 on rebuild; the tracer's
    offset re-anchors so recorded spans never go backwards."""
    tr = Tracer("t")
    tr.record("round 0", cat="round", track="server", v_start=0.0, v_end=5.0)
    assert tr.last_virtual_end() == 5.0
    tr.set_virtual_offset(tr.last_virtual_end())
    tr.record("round 1", cat="round", track="server", v_start=0.0, v_end=5.0)
    rounds = sorted(tr.by_cat("round"), key=lambda s: s.v_start)
    assert [(s.v_start, s.v_end) for s in rounds] == [(0.0, 5.0), (5.0, 10.0)]


def test_chrome_trace_export_is_schema_valid(tmp_path):
    tr = Tracer("t")
    parent = tr.record("round 0", cat="round", track="server",
                       v_start=0.0, v_end=2.0,
                       args={"bad": float("nan"), "ok": 1})
    tr.record("up c0", cat="uplink", track="c0", v_start=1.0, v_end=2.0,
              parent=parent)
    obj = tr.to_chrome()
    assert validate_chrome_trace(obj) == 2
    # non-finite args are stringified so the export stays strict JSON
    x = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert all(isinstance(e["args"]["bad"], str) for e in x
               if "bad" in e.get("args", {}))
    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    with open(path) as f:
        assert validate_chrome_trace(json.load(f)) == 2


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "pid": 1, "tid": 1,
             "ts": float("nan"), "dur": 1.0}]})


def test_span_names_are_defined_once_under_one_prefix():
    """The round's span names on the profiler's clock are six distinct
    constants in obs/trace.py, all under the ``fsl.`` prefix that sets
    them apart from the benchmark harness's ``bench.`` spans."""
    from repro.obs import trace as ot
    names = (ot.SPAN_ROUND, ot.SPAN_INPUT, ot.SPAN_CLIENT_STEP,
             ot.SPAN_SYNC, ot.SPAN_REDUCE, ot.SPAN_GENERATOR)
    assert len(set(names)) == 6
    assert all(n.startswith("fsl.") for n in names)
    assert sorted(k for k in vars(ot) if k.startswith("SPAN_")) == sorted(
        ["SPAN_ROUND", "SPAN_INPUT", "SPAN_CLIENT_STEP", "SPAN_SYNC",
         "SPAN_REDUCE", "SPAN_GENERATOR"])


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_registry_types_and_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("wire.up_bytes")
    c.inc(10)
    c.inc(5)
    assert c.value == 15
    with pytest.raises(ValueError):
        c.inc(-1)
    reg.gauge("fed.round_time_s").set(2.5)
    h = reg.histogram("fed.client_finish_s")
    for v in (1.0, 2.0, 4.0):
        h.observe(v)
    assert h.count == 3 and h.mean == pytest.approx(7.0 / 3.0)
    assert h.quantile(0.0) <= h.quantile(1.0)
    with pytest.raises(TypeError):
        reg.gauge("wire.up_bytes")      # registered as a counter
    snap = reg.snapshot()
    assert snap["wire.up_bytes"]["value"] == 15
    assert "fed.client_finish_s" in reg


def test_jsonl_sink_round_trips(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with JsonlSink(path) as sink:
        sink.write({"a": 1})
        sink.write({"b": [1.5, 2.5]})
    rows = load_jsonl(path)
    assert rows == [{"a": 1}, {"b": [1.5, 2.5]}]


# ---------------------------------------------------------------------------
# record + replay
# ---------------------------------------------------------------------------

def test_knobs_serialization_round_trips_bit_exactly():
    cfg = _cfg(**{"split.enabled": True})
    k = knobs_from_config(cfg)
    k2 = k.replace(codec="int8", deadline_s=12.345678901234567,
                   stage_by_boundary={0: "dp", 1: "int8"})
    back = knobs_from_dict(json.loads(json.dumps(knobs_to_dict(k2))))
    assert back == k2                   # frozen dataclass, bit-exact floats
    assert all(isinstance(b, int) for b in back.stage_by_boundary)


def test_feedback_serialization_round_trips(recorded_run):
    tr, run_dir = recorded_run
    for fb in tr.feedback:
        d = json.loads(json.dumps(feedback_to_dict(fb)))
        back = feedback_from_dict(d)
        # NaN != NaN breaks equality; compare the serialized text forms
        assert (json.dumps(feedback_to_dict(back), sort_keys=True)
                == json.dumps(feedback_to_dict(fb), sort_keys=True))
        assert back.round_index == fb.round_index
        assert back.client_finish_s == fb.client_finish_s


def test_recorded_run_writes_all_artifacts(recorded_run):
    _, run_dir = recorded_run
    for name in ("manifest.json", "feedback.jsonl", "knobs.jsonl",
                 "metrics.jsonl", "trace.json"):
        assert os.path.exists(os.path.join(run_dir, name)), name
    rec = load_run(run_dir)
    assert rec.num_rounds == 3
    assert len(rec.knobs) == 3
    assert rec.manifest["config"]["control"]["mode"] == "adaptive"


def test_replay_reproduces_live_knob_decisions_bit_exactly(recorded_run):
    """ISSUE 6 acceptance pin: the recorded RoundFeedback JSONL replayed
    offline through the PR-5 controllers reproduces the live knob
    sequence bit-exactly."""
    tr, run_dir = recorded_run
    res = replay_run(run_dir)
    assert res.matches, res.diff()
    assert len(res.decisions) == 3
    # the offline decisions ARE the recorded ControlKnobs, field for field
    for dec, rec in zip(res.decisions, load_run(run_dir).knobs):
        assert dec == rec


def test_replay_decisions_is_the_controller_fold(recorded_run):
    """decision_r = suite(history[:r], decision_{r-1}) with decision_{-1}
    = knobs_from_config — the exact fold the trainer applies live."""
    tr, run_dir = recorded_run
    from repro.obs.replay import suite_from_manifest
    rec = load_run(run_dir)
    suite = suite_from_manifest(rec.manifest)
    decisions = replay_decisions(suite, rec.feedback,
                                 knobs_from_config(tr.cfg))
    assert decisions == rec.knobs


def test_replay_requires_manifest(tmp_path):
    with pytest.raises(FileNotFoundError):
        replay_run(str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# engine spans
# ---------------------------------------------------------------------------

def test_split_round_traces_every_boundary_crossing(recorded_run):
    tr, _ = recorded_run
    spans = tr.recorder.tracer.spans
    cats = {s.cat for s in spans}
    assert {"round", "downlink", "client", "batch", "segment", "boundary",
            "uplink", "aggregate"} <= cats
    # every LAN boundary of every traced batch appears fwd AND bwd
    hops = [s for s in spans if s.cat == "boundary"]
    batches = [s for s in spans if s.cat == "batch"]
    crossings_per_batch = {}
    for cid, ex in tr.split_execs.items():
        crossings_per_batch[cid] = 2 * ex.num_boundaries
    expect = sum(crossings_per_batch[s.track] for s in batches)
    assert expect == 0 or len(hops) == expect
    for h in hops:
        assert {"boundary", "direction"} <= set(h.args)


def test_spans_nest_on_the_virtual_clock(recorded_run):
    tr, _ = recorded_run
    tracer = tr.recorder.tracer
    tol = 1e-6
    for s in tracer.spans:
        if s.parent_id is None or not s.has_virtual:
            continue
        p = tracer.by_id(s.parent_id)
        if p is None or not p.has_virtual:
            continue
        assert p.v_start - tol <= s.v_start, (p.name, s.name)
        assert s.v_end <= p.v_end + tol, (p.name, s.name)


def test_round_spans_monotone_across_epochs(recorded_run):
    tr, _ = recorded_run
    rounds = sorted(tr.recorder.tracer.by_cat("round"),
                    key=lambda s: s.v_start)
    assert len(rounds) == 3
    for a, b in zip(rounds, rounds[1:]):
        assert a.v_end <= b.v_start + 1e-9
    # the trace clock is the feedback clock
    assert rounds[-1].v_end == pytest.approx(tr.feedback[-1].clock_s)


def test_async_engine_emits_spans(tmp_path, parts):
    cfg = _cfg(**{"fed.mode": "fedasync", "obs.enabled": True,
                  "obs.out_dir": str(tmp_path), "obs.run_id": "a"})
    tr = FSLGANTrainer(cfg, parts, seed=0)
    tr.train_epoch(batches_per_client=2)
    cats = {s.cat for s in tr.recorder.tracer.spans}
    assert {"round", "downlink", "client", "uplink", "aggregate"} <= cats
    tr.recorder.flush()
    with open(os.path.join(str(tmp_path), "a", "trace.json")) as f:
        assert validate_chrome_trace(json.load(f)) > 0


# ---------------------------------------------------------------------------
# obs never steers
# ---------------------------------------------------------------------------

def test_obs_on_is_bit_exact_with_obs_off(tmp_path, parts):
    losses = {}
    for on in (False, True):
        over = {"split.enabled": True}
        if on:
            over.update({"obs.enabled": True, "obs.out_dir": str(tmp_path),
                         "obs.run_id": "x"})
        tr = FSLGANTrainer(_cfg(**over), parts, seed=0)
        hist = []
        for _ in range(2):
            m = tr.train_epoch(batches_per_client=2)
            hist.append((m["d_loss"], m["g_loss"], m["round_time_s"]))
        losses[on] = hist
    assert losses[False] == losses[True]


def test_profiling_gated_off_by_default(recorded_run):
    _, run_dir = recorded_run
    assert not os.path.exists(os.path.join(run_dir, "profile.json"))


def test_profiling_writes_roofline_terms_when_enabled(tmp_path, parts):
    cfg = _cfg(**{"obs.enabled": True, "obs.out_dir": str(tmp_path),
                  "obs.run_id": "p", "obs.profile_kernels": True})
    tr = FSLGANTrainer(cfg, parts, seed=0)
    tr.train_epoch(batches_per_client=1)
    with open(os.path.join(str(tmp_path), "p", "profile.json")) as f:
        prof = json.load(f)
    names = list(prof)
    assert any(n.startswith("fedavg") for n in names)
    dev = jax.devices()[0]
    for p in prof.values():
        assert p["compile_s"] > 0 and p["run_s"] > 0
        assert p["flops"] >= 0 and p["device_kind"] == dev.device_kind
        # terms come from the running device's peaks; the host CPU has none
        if dev.platform == "cpu":
            assert p["compute_term_s"] is None
        else:
            assert p["compute_term_s"] >= 0


# ---------------------------------------------------------------------------
# flush idempotence (ISSUE 7 satellite: the _obs.py double-flush path)
# ---------------------------------------------------------------------------

def test_flush_is_idempotent(recorded_run):
    """A second flush with no new spans must not re-export the trace —
    finish() flushing and its caller flushing again costs one export."""
    tr, run_dir = recorded_run
    rec = tr.recorder
    path = rec.flush()
    assert path == os.path.join(run_dir, "trace.json")
    mtime = os.path.getmtime(path)
    with open(path) as f:
        before = f.read()
    os.utime(path, (mtime - 10, mtime - 10))     # make any rewrite visible
    assert rec.flush() == path                   # cached path, no export
    assert os.path.getmtime(path) == pytest.approx(mtime - 10)
    with open(path) as f:
        assert f.read() == before
    # new spans re-arm the export
    rec.tracer.record("probe", cat="round", track="server",
                      v_start=0.0, v_end=0.0)
    assert rec.flush() == path
    assert os.path.getmtime(path) > mtime - 10


# ---------------------------------------------------------------------------
# digests: artifact-level bit-exactness pins (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

def _state_digests(tr):
    """Recompute the committed-state digest sequence a recorder would have
    written for this trainer's CURRENT state (single round boundary)."""
    from repro.obs import state_digest
    st = tr.state
    cid0 = tr._active_clients()[0]
    return state_digest(st.d_params[cid0], st.d_opt, st.g_params,
                        st.g_opt, round_index=st.step - 1)


def test_recorded_run_writes_digests_and_alert_sink(recorded_run):
    _, run_dir = recorded_run
    rec = load_run(run_dir)
    assert len(rec.digests) == 3
    assert [d.round_index for d in rec.digests] == [0, 1, 2]
    for d in rec.digests:
        assert len(d.global_digest) == 32 and len(d.opt_digest) == 32
        assert not d.rolled_back
        assert d.global_sketch[0] > 0            # L2 of a real tree
    # the committed digest equals the engine's as-aggregated digest in a
    # healthy run (no health action ever touched the tree)
    for d in rec.digests:
        assert d.aggregated_digest == d.global_digest


def test_digests_obs_on_matches_obs_off_state(tmp_path, parts):
    """obs-on == obs-off, at the artifact level: the digests a recorded
    run persists equal digests recomputed from an identical run that
    never recorded anything."""
    cfg_on = _cfg(**{"obs.enabled": True, "obs.out_dir": str(tmp_path),
                     "obs.run_id": "don"})
    tr_on = FSLGANTrainer(cfg_on, parts, seed=0)
    tr_off = FSLGANTrainer(_cfg(), parts, seed=0)
    off_digests = []
    for _ in range(2):
        tr_on.train_epoch(batches_per_client=2)
        tr_off.train_epoch(batches_per_client=2)
        off_digests.append(_state_digests(tr_off))
    rec = load_run(os.path.join(str(tmp_path), "don"))
    assert [d.global_digest for d in rec.digests] \
        == [d.global_digest for d in off_digests]
    assert [d.opt_digest for d in rec.digests] \
        == [d.opt_digest for d in off_digests]
    assert [d.gan_digest for d in rec.digests] \
        == [d.gan_digest for d in off_digests]


def test_digests_loop_vs_vectorized_backend(tmp_path, parts):
    """Cross-backend digest stability: loop and vectorized dispatch are a
    TOLERANCE pin (different XLA programs, ~1e-5 fp32 drift — same bound
    as the in-memory pin in test_fed_runtime), so their digest *sketches*
    must agree tightly while diff.py classifies the digest mismatch as
    numeric divergence at equal knobs."""
    import numpy as np
    from repro.obs import diff_runs
    dirs = {}
    for backend in ("loop", "vectorized"):
        cfg = _cfg(**{"fed.backend": backend, "obs.enabled": True,
                      "obs.out_dir": str(tmp_path),
                      "obs.run_id": f"b_{backend}"})
        tr = FSLGANTrainer(cfg, parts, seed=0)
        for _ in range(2):
            tr.train_epoch(batches_per_client=2)
        dirs[backend] = os.path.join(str(tmp_path), f"b_{backend}")
    ra = load_run(dirs["loop"])
    rb = load_run(dirs["vectorized"])
    for da, db in zip(ra.digests, rb.digests):
        np.testing.assert_allclose(da.global_sketch[:3], db.global_sketch[:3],
                                   rtol=1e-4, atol=1e-5)
        assert da.global_sketch[3] == db.global_sketch[3]   # leaf counts
    d = diff_runs(dirs["loop"], dirs["vectorized"])
    fd = d.first_divergence
    assert fd is not None and fd.kind == "numeric"
    assert fd.field.startswith("digest.")
    # the knobs never diverged — no controller-kind entries at all
    assert not any(e.kind == "controller" for e in d.entries)


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_obs_section_validates_names_at_construction():
    from repro.config import ObsConfig
    with pytest.raises(ValueError):
        ObsConfig(sinks=("trace", "punchcard"))
    cfg = _cfg(**{"obs.enabled": True, "obs.sinks": ["trace"]})
    assert cfg.obs.sinks == ("trace",)
    assert cfg.to_dict()["obs"]["enabled"] is True
