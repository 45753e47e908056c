"""Flight-recorder trace walkthrough (ISSUE 6): run a federated split
round with tracing on, export Chrome-trace JSON, and read it back —
plus the watchtower layer on top (ISSUE 7): health alerts and per-round
state digests printed alongside the spans.

The engine emits nested spans on its discrete-event virtual clock for
round -> downlink -> client execution -> batch -> split segment ->
boundary crossing -> uplink -> aggregate.  The exporter writes the
standard Chrome trace format, so the output opens directly in
`chrome://tracing` or https://ui.perfetto.dev — drag the file in and the
round unfolds as a timeline: one server track plus one track per client,
with every LAN boundary crossing (activation fwd, activation-grad bwd)
visible inside each batch.

Run: PYTHONPATH=src python examples/trace_viewer_demo.py
     -> writes obs_runs/trace-demo/trace.json
"""
import json
import os
from collections import Counter

from repro.configs.registry import get_config
from repro.core.gan import FSLGANTrainer
from repro.data import partition_dirichlet, synthetic_mnist
from repro.launch.cache import use_compile_cache
from repro.obs import validate_chrome_trace

CLIENTS = 2
OUT = os.path.join("obs_runs")


def main():
    cfg = get_config("dcgan-mnist").override({
        "shape.global_batch": 8,
        "fsl.num_clients": CLIENTS,
        "model.dcgan.base_filters": 8,
        "split.enabled": True,
        "fed.client_local_steps": {"c1": 2},   # a visible straggler tail
        "obs.enabled": True,
        "obs.out_dir": OUT,
        "obs.run_id": "trace-demo",
        # the watchtower (ISSUE 7): numeric-health monitors on every
        # round, warn-only policy — a healthy demo prints zero alerts
        "obs.health.enabled": True,
        "obs.health.policy": "warn",
    })
    imgs, labels = synthetic_mnist(60 * CLIENTS, seed=0)
    parts = partition_dirichlet(imgs, labels, CLIENTS, alpha=0.5, seed=0)
    tr = FSLGANTrainer(cfg, parts, seed=0)

    print("== two traced federated split rounds ==")
    for _ in range(2):
        m = tr.train_epoch(batches_per_client=2)
        print(f"  d_loss {m['d_loss']:.4f}  round {m['round_time_s']:.1f}s "
              f"(virtual)")
    tr.recorder.flush()

    trace_path = tr.recorder.path("trace.json")
    with open(trace_path) as f:
        obj = json.load(f)
    n = validate_chrome_trace(obj)
    print(f"\n== {trace_path}: {n} events, schema-valid ==")
    cats = Counter(s.cat for s in tr.recorder.tracer.spans)
    for cat in ("round", "downlink", "client", "batch", "segment",
                "boundary", "uplink", "aggregate"):
        print(f"  {cat:>9}: {cats.get(cat, 0):>3} spans")

    print("\n== one batch, span by span (virtual clock) ==")
    tracer = tr.recorder.tracer
    batch = min(tracer.by_cat("batch"), key=lambda s: s.v_start)
    print(f"  {batch.name} on {batch.track}: "
          f"[{batch.v_start:.2f}, {batch.v_end:.2f}]s")
    for child in sorted(tracer.children(batch.span_id),
                        key=lambda s: s.v_start):
        tag = (f" ({child.args.get('direction')} b"
               f"{child.args.get('boundary')})"
               if child.cat == "boundary" else "")
        print(f"    {child.v_start:9.3f} -> {child.v_end:9.3f}  "
              f"{child.cat:>8}  {child.name}{tag}")

    print("\n== watchtower: health alerts + state digests ==")
    if tr.health_alerts:
        for a in tr.health_alerts:
            print(f"  round {a.round_index} [{a.severity:>5}] "
                  f"{a.check}: {a.message}")
    else:
        print("  no health alerts (all monitors quiet — see "
              "alerts.jsonl for the persisted record)")
    for d in tr.recorder.digests:
        print(f"  round {d.round_index} global digest {d.global_digest} "
              f"l2={d.global_sketch[0]:.4f}"
              f"{'  (ROLLED BACK)' if d.rolled_back else ''}")

    print(f"\nopen {trace_path} in chrome://tracing or ui.perfetto.dev — "
          "pid 1 is the virtual clock, one thread per client track.")


if __name__ == "__main__":
    use_compile_cache()
    main()
