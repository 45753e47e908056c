"""Readings that the limits of a cell's comparison are set from.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 ...

For each seed, in this one process: the program's set-up rounds as a run
makes them, then the plain reference, and three stand-ins for the
program, each compared with the reference by the run's own comparison:

  * ``program``: the trainer (the lower reading of each number);
  * ``control``: the reference computed in bfloat16, the precision below
    the configuration's float32;
  * ``half_batch``: the reference with the second half of every batch
    left out and the mean taken over the rest;

and the numbers that hold the program's draws (``draws``).

A fault that returns the state unchanged reads 1 on ``change_gap`` by
its definition and needs no run.  Prints one JSON line per seed.  Not
part of a benchmark run.
"""
import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(name: str, seed: int, *, require_tpu: bool = True,
             tweak=None) -> dict:
    from bench import check, harness
    spec, work, conf, cell = harness.load_cell(name)
    if tweak is not None:
        conf, cell = tweak(conf, cell)
    harness.chips(int(work["chips"]), require_tpu)
    system = importlib.import_module(
        f"bench.systems.{conf['family']}").System(
            conf, cell, harness.seeds_from(seed))
    prog = harness.setup_rounds(system, int(cell["check_rounds"]))
    system.release()
    t = time.perf_counter()
    ref = system.reference()
    out = {"seed": seed, "reference_s": time.perf_counter() - t,
           "reference_losses": ref["losses"],
           "draws": {k: v[0] for k, v in system.draw_numbers().items()}}
    stand_ins = [
        ("program", prog),
        ("control", system.reference(dtype="bfloat16",
                                     precision="default")),
        ("half_batch", system.reference(half_batch=True))]
    for label, readings_ in stand_ins:
        numbers = check.compare(readings_, ref, system.init_host)
        out[label] = {k: v[0] for k, v in numbers.items()}
        out[label + "_where"] = {k: v[1] for k, v in numbers.items()}
        out[label + "_losses"] = readings_["losses"]
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    a = p.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    for s in a.seeds:
        print(json.dumps(readings(a.workload, s)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
